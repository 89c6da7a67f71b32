"""The two latent-attention kernels alone on the chip, at the shapes of
the benchmark's long-context cell: parity with their XLA oracles, and
each one's time against its roofline (ops/mla_attention.py;
benchmark/architectures/pangu_ultra_moe.py keeps the operation and byte
counts; benchmark/peaks.json the chip's peaks).

    python tools/check_mla_kernels.py

``mla_decode_attention``: 32 rows, int8 pool, contexts 512 / 2,048 /
4,096. ``mla_prefill_attention``: one row, a 256-token chunk at offsets
0 / 1,024 / 3,072. One ``VERDICT ...: PASS|FAIL`` line a case, then one
JSON line a case with the time, the roofline time and which side bounds
it; all of it also in ``chiprun_out/mla_kernels.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def timed(fn, *args, iters: int = 20) -> float:
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import manifest, roofline
    from p2p_llm_chat_tpu.models.configs import get_config
    from p2p_llm_chat_tpu.ops import mla_attention as mla
    from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache

    name = "openpangu-ultra-moe-718b-l9e16"
    cfg = manifest.load_cell(name + ".long-context", ROOT).config
    arch = manifest.load_architecture(os.path.join(ROOT, "benchmark"),
                                      cfg["architecture"])
    config = get_config(name)
    dev = jax.devices()[0]
    peaks = roofline.peaks_for(dev.device_kind)
    flops_s, bytes_s = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    rows_out = []

    def report(kernel, case, ok, err, secs, flops, nbytes):
        t_f, t_b = flops / flops_s, nbytes / bytes_s
        print(f"VERDICT {kernel} {case}: {'PASS' if ok else 'FAIL'} "
              f"(max err {err:.3g})", flush=True)
        row = {"kernel": kernel, "case": case, "ok": bool(ok),
               "max_err": float(err), "ms": secs * 1e3,
               "flops": flops, "bytes": nbytes,
               "roofline_ms": max(t_f, t_b) * 1e3,
               "bound": "compute" if t_f >= t_b else "memory",
               "roofline_share_pct": 100.0 * max(t_f, t_b) / secs,
               "device": dev.device_kind}
        rows_out.append(row)
        print(json.dumps(row), flush=True)

    # -- decode ---------------------------------------------------------
    B, Hq, r, vd, ps = 32, config.num_heads, config.cache_k_dim, \
        config.cache_v_dim, 64
    L, per_row = config.num_layers, 64
    rng = np.random.default_rng(0)
    pool = PagedKVCache.create(config, B, 1 + B * per_row, ps,
                               max_pages_per_row=per_row, quantized=True)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    pool = pool._replace(
        k=jax.random.randint(ks[0], pool.k.shape, -127, 128, jnp.int8),
        v=jnp.pad(jax.random.randint(
            ks[1], pool.v.shape[:-1] + (64,), -127, 128, jnp.int8),
            ((0, 0),) * 4 + ((0, vd - 64),)),
        k_scale=jax.random.uniform(ks[2], pool.k_scale.shape, jnp.float32,
                                   0.005, 0.02),
        v_scale=jax.random.uniform(ks[3], pool.v_scale.shape, jnp.float32,
                                   0.005, 0.02),
        page_table=1 + jnp.arange(B * per_row, dtype=jnp.int32
                                  ).reshape(B, per_row))
    ql = jax.random.normal(ks[4], (B, Hq, r), jnp.bfloat16)
    qr = jnp.pad(jax.random.normal(ks[5], (B, Hq, 64), jnp.bfloat16),
                 ((0, 0), (0, 0), (0, vd - 64)))
    cc = jax.random.normal(ks[6], (B, r), jnp.bfloat16)
    rc = jnp.pad(jax.random.normal(ks[7], (B, 64), jnp.bfloat16),
                 ((0, 0), (0, vd - 64)))
    sm = 192 ** -0.5
    for ctx in (512, 2048, 4096):
        pages = ctx // ps
        lengths = jnp.asarray(rng.integers(ctx - ps, ctx, B), jnp.int32)
        cache = pool._replace(lengths=lengths)
        kern = jax.jit(lambda q1, q2, c1, c2, ch, ln: mla.mla_decode_attention(
            q1, q2, c1, c2, ch, ln, 3, pages=pages, sm_scale=sm,
            impl="kernel"))
        ref = jax.jit(lambda q1, q2, c1, c2, ch, ln: mla.mla_decode_reference(
            q1, q2, c1, c2, ch, ln, 3, pages=pages, sm_scale=sm))
        got = kern(ql, qr, cc, rc, cache, lengths)
        want = ref(ql, qr, cc, rc, cache, lengths)
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(want)))
        secs = timed(kern, ql, qr, cc, rc, cache, lengths)
        flops, nbytes = arch.mla_decode_cost(cfg, B, int(jnp.mean(lengths)))
        report("mla_decode_attention", f"rows32 ctx{ctx} int8",
               err <= 0.03 * scale, err, secs, flops, nbytes)

    # -- prefill --------------------------------------------------------
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    S = 256
    for off in (0, 1024, 3072):
        W = off + S
        k2 = jax.random.split(jax.random.PRNGKey(off + 1), 4)
        qn = jax.random.normal(k2[0], (1, S, Hq, dn), jnp.bfloat16)
        qrr = jax.random.normal(k2[1], (1, S, Hq, dr), jnp.bfloat16)
        kv = jax.random.normal(k2[2], (1, W, Hq * (dn + dv)), jnp.bfloat16)
        kr = jnp.pad(jax.random.normal(k2[3], (1, W, dr), jnp.bfloat16),
                     ((0, 0), (0, 0), (0, vd - dr)))
        kern = jax.jit(lambda a, b, c, d: mla.mla_prefill_attention(
            a, b, c, d, off, dn=dn, dr=dr, dv=dv, impl="kernel"))
        ref = jax.jit(lambda a, b, c, d: mla.mla_prefill_reference(
            a, b, c, d, off, dn=dn, dr=dr, dv=dv))
        got = kern(qn, qrr, kv, kr).astype(jnp.float32)
        want = ref(qn, qrr, kv, kr).astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(want)))
        secs = timed(kern, qn, qrr, kv, kr)
        flops, nbytes = arch.mla_prefill_cost(cfg, S, W)
        report("mla_prefill_attention", f"chunk256 offset{off}",
               err <= 0.03 * scale, err, secs, flops, nbytes)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "mla_kernels.json"), "w") as f:
        json.dump(rows_out, f, indent=1)
    if not all(r["ok"] for r in rows_out):
        sys.exit(1)


if __name__ == "__main__":
    main()

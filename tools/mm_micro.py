"""Microbench: decode-shaped matmul implementations on real hardware.

Times one [rows, H] @ [H, O] matmul per variant at bench-1b decode shapes
to locate the w8a16 floor (tools/profile_step.py showed the fused-matmul
scan at ~2.5 ms vs a ~1.3 ms HBM bound — convert/MXU compute, not DMA,
is the suspect).

Variants:
- w8a16: ops/quant_mm.quant_matmul (current production kernel)
- bf16:  plain XLA bf16 matmul
- w8a8:  Pallas int8 x int8 -> int32 MXU dot with dynamic per-row
         activation scales (prototype)
- xla8:  XLA lax.dot_general(int8, int8) -> int32 (does XLA stream it?)
"""

from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from p2p_llm_chat_tpu.models.quant import quantize  # noqa: E402
from p2p_llm_chat_tpu.ops.quant_mm import quant_matmul  # noqa: E402

SHAPES = [  # (H, O) per bench-1b fused layer + lm_head
    (2048, 4096),    # wqkv
    (2048, 2048),    # wo
    (2048, 11264),   # wgu
    (5632, 2048),    # w_down
    (2048, 32768),   # lm_head
]
ROWS = 32


def _w8a8_kernel(xq_ref, xs_ref, q_ref, s_ref, o_ref):
    xq = xq_ref[...]                               # [rows, H] int8
    q = q_ref[...]                                 # [H, bo] int8
    acc = jax.lax.dot(xq, q, preferred_element_type=jnp.int32)
    s = s_ref[0].astype(jnp.float32)               # [bo]
    xs = xs_ref[...].astype(jnp.float32)           # [rows, 1]
    o_ref[...] = (acc.astype(jnp.float32) * s[None, :] * xs).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=())
def w8a8_matmul(x, q, s):
    rows, H = x.shape
    O = q.shape[1]
    # dynamic per-row symmetric int8 activation quant
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    xs = jnp.where(amax > 0, amax / 127.0, 1.0)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / xs), -127,
                  127).astype(jnp.int8)
    bo = 512 if O % 512 == 0 else 1024
    while H * bo > 4 * 1024 * 1024:
        bo //= 2
    out = pl.pallas_call(
        _w8a8_kernel,
        grid=(O // bo,),
        in_specs=[
            pl.BlockSpec((rows, H), lambda i: (0, 0)),
            pl.BlockSpec((rows, 1), lambda i: (0, 0)),
            pl.BlockSpec((H, bo), lambda i: (0, i)),
            pl.BlockSpec((1, bo), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((rows, bo), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, O), jnp.bfloat16),
    )(xq, xs, q, s)
    return out


def timeit(name, fn, x, *args, iters=200):
    """Loop the op INSIDE one jitted scan (the carry feeds the next
    iteration so XLA cannot hoist it) — the per-dispatch cost lands on
    ONE dispatch instead of one per op."""
    H = x.shape[1]

    def run_n(n, x0):
        def body(c, _):
            out = fn(c, *args)
            nxt = (c + out.astype(c.dtype)[:, :H] * 1e-6
                   if out.shape[1] >= H else
                   c.at[:, : out.shape[1]].add(out.astype(c.dtype) * 1e-6))
            return nxt, ()
        c, _ = jax.lax.scan(body, x0, None, length=n)
        return c

    def wall(r):
        np.asarray(jax.device_get(r(x)).ravel()[:1])      # compile + warm
        best = float("inf")
        for _ in range(3):
            t = time.monotonic()
            np.asarray(jax.device_get(r(x)).ravel()[:1])
            best = min(best, time.monotonic() - t)
        return best

    # Two scan lengths solve out the constant per-dispatch cost c:
    # wall(N) = c + N * op.
    n1, n2 = iters // 4, iters
    w1 = wall(jax.jit(functools.partial(run_n, n1)))
    w2 = wall(jax.jit(functools.partial(run_n, n2)))
    dev = (w2 - w1) / (n2 - n1)
    print(f"  {name:10s} {dev*1e6:9.1f} us", flush=True)
    return dev


def main():
    key = jax.random.PRNGKey(0)
    total = {}
    for H, O in SHAPES:
        print(f"[{ROWS}x{H}] @ [{H}x{O}]  (int8 stripe {H*O/1e6:.0f} MB, "
              f"bound ~{H*O/819e9*1e6:.0f} us)")
        x = jax.random.normal(key, (ROWS, H), jnp.bfloat16)
        w = jax.random.normal(key, (H, O), jnp.float32)
        qt = quantize(w)
        wb = w.astype(jnp.bfloat16)
        jax.block_until_ready((x, qt, wb))
        def xla8(a, q, s):
            amax = jnp.max(jnp.abs(a.astype(jnp.float32)), -1, keepdims=True)
            xs = jnp.where(amax > 0, amax / 127.0, 1.0)
            aq = jnp.clip(jnp.round(a.astype(jnp.float32) / xs), -127,
                          127).astype(jnp.int8)
            acc = jax.lax.dot_general(aq, q, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.int32)
            return (acc.astype(jnp.float32) * s * xs).astype(jnp.bfloat16)

        t1 = timeit("w8a16", quant_matmul, x, qt.q, qt.s)
        t2 = timeit("bf16", lambda a, b: a @ b, x, wb)
        t3 = timeit("w8a8", w8a8_matmul, x, qt.q, qt.s)
        t4 = timeit("xla8", xla8, x, qt.q, qt.s)
        for k, t in (("w8a16", t1), ("bf16", t2), ("w8a8", t3), ("xla8", t4)):
            total[k] = total.get(k, 0.0) + t
    print("totals (one layer-set walk):")
    for k, t in total.items():
        print(f"  {k:10s} {t*1e6:9.1f} us")


if __name__ == "__main__":
    main()

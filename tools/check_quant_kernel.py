"""TPU parity + timing check: Pallas quantized matmuls vs forced XLA.

Runs the w8a16 and w4a16 kernels (ops/quant_mm.py — stacked and
unstacked) on the real chip over random weights and checks closeness
to the explicit-dequant XLA path, then times both at decode rows. CPU
tests cover the math in interpret mode; this is the Mosaic-lowering
check, and the measurement behind the per-hidden-size tile autotune
table (_TILE_TABLE) of the one-matrix grids and, as ``sweep-cells``,
behind the expert grid's own rule (pick_expert_bo: the widest stripe
that fits, at every expert matmul a benchmark cell dispatches).

Every shape runs to the end and gets one verdict line: ``PASS``
(compiled, matches XLA), ``FAIL`` (the compiler refused it, it
diverged, or it ran out of memory — with the reason), and beside a
pass whether the kernel ``loses to XLA`` on time (the
kernel-never-loses bar ROADMAP S5 settles; printed, not part of the
exit code). The exit code is non-zero when any shape FAILs.

The shape matrix covers the serving configs' decode projections:
hidden 1024 (draft-400m), 2048 (bench-1b), and every llama3.1-8b
projection — K=4096 -> 6144 (fused qkv) / 4096 / 28672 (fused
gate|up), K=14336 -> 4096, and the 4096 x 128256 head.
"""

from __future__ import annotations

import functools
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from p2p_llm_chat_tpu.models.quant import (QTensor, QTensor4,  # noqa: E402
                                           _int4_group, dequantize,
                                           dequantize4, quantize, quantize4)
from tools.kernel_verdicts import (SlowerThanXLA, require_tpu,  # noqa: E402
                                   run_cases)
from p2p_llm_chat_tpu.ops import quant_mm as qmm  # noqa: E402
from p2p_llm_chat_tpu.ops.quant_mm import (_pick_1d_bo,  # noqa: E402
                                           pick_expert_bo, pick_int4_bo,
                                           quant_matmul, quant_matmul4,
                                           quant_matmul_experts_stacked,
                                           quant_matmul_experts_stacked4,
                                           quant_matmul_stacked,
                                           quant_matmul_stacked4)

ROWS = 32          # serving decode batch
EXPERT_ROWS = 16   # per-expert capacity bucket at decode (B=32, top-2/8)
STEPS = 20


def _time_ms(fn) -> float:
    r = fn()                                   # compile + warm
    np.asarray(r).ravel()[:1]
    t = time.monotonic()
    for _ in range(STEPS):
        r = fn()
    np.asarray(r).ravel()[:1]                  # forced sync
    return (time.monotonic() - t) / STEPS * 1e3


def _quantized(seed: int, shape: tuple, quantize_fn):
    """Random f32 weights ``[L, ...]`` made and quantized ON the device
    (host generation of the 6 GB expert stacks costs minutes of chip
    time), one layer at a time: a whole expert stack in f32 plus the
    quantizer's temporaries does not fit a 16 GB chip."""
    qfn = jax.jit(quantize_fn)
    layers = [qfn(jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), layer), shape[1:],
        jnp.float32)) for layer in range(shape[0])]
    return type(layers[0])(q=jnp.stack([t.q for t in layers]),
                           s=jnp.stack([t.s for t in layers]))


def run8(H: int, O: int, L: int = 2) -> None:
    """w8a16: stacked + unstacked kernel vs forced-XLA dequant — parity
    (roundoff-only: both sides see the same int8 weights) and timing."""
    rng = np.random.default_rng(H + O)
    x = jnp.asarray(rng.standard_normal((ROWS, H), np.float32),
                    jnp.bfloat16)
    qt = _quantized(H + O, (L, H, O), quantize)

    xla = jax.jit(lambda x, q, s: x @ dequantize(QTensor(q=q, s=s),
                                                 x.dtype))
    for layer in (0, L - 1):
        got = np.asarray(quant_matmul_stacked(x, qt.q, qt.s, layer),
                         np.float32)
        ref = np.asarray(xla(x, qt.q[layer], qt.s[layer]), np.float32)
        err = np.max(np.abs(got - ref))
        denom = np.max(np.abs(ref)) or 1.0
        print(f"int8 stacked H={H} O={O} layer={layer}: rel "
              f"{err / denom:.5f}")
        assert err / denom < 2e-2, "w8a16 stacked kernel diverges"
    got = np.asarray(quant_matmul(x, qt.q[0], qt.s[0]), np.float32)
    ref = np.asarray(xla(x, qt.q[0], qt.s[0]), np.float32)
    assert np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) or 1.0) < 2e-2

    k_ms = _time_ms(lambda: quant_matmul_stacked(x, qt.q, qt.s, 1))
    x_ms = _time_ms(lambda: xla(x, qt.q[1], qt.s[1]))
    bo = _pick_1d_bo(ROWS, H, O, 2)
    print(f"int8 H={H} O={O} (1d bo={bo}): kernel {k_ms:.4f} ms vs XLA "
          f"{x_ms:.4f} ms ({x_ms / k_ms:.2f}x)")
    if k_ms > x_ms * 1.02:
        raise SlowerThanXLA(f"kernel {k_ms:.4f} ms vs XLA {x_ms:.4f} ms")


def run4(H: int, O: int, L: int = 2) -> None:
    """w4a16: stacked + unstacked kernel vs forced-XLA group dequant."""
    rng = np.random.default_rng(H + O + 1)
    x = jnp.asarray(rng.standard_normal((ROWS, H), np.float32),
                    jnp.bfloat16)
    qt = _quantized(H + O + 1, (L, H, O), quantize4)
    ng = qt.s.shape[-2]
    bo = pick_int4_bo(ROWS, H, O, ng, 2)
    assert bo is not None, f"w4a16 kernel must cover H={H} O={O} ng={ng}"

    xla = jax.jit(lambda x, q, s: x @ dequantize4(QTensor4(q=q, s=s),
                                                  x.dtype))
    for layer in (0, L - 1):
        got = np.asarray(quant_matmul_stacked4(x, qt.q, qt.s, layer),
                         np.float32)
        ref = np.asarray(xla(x, qt.q[layer], qt.s[layer]), np.float32)
        err = np.max(np.abs(got - ref))
        denom = np.max(np.abs(ref)) or 1.0
        print(f"int4 stacked H={H} O={O} layer={layer}: rel "
              f"{err / denom:.5f}")
        assert err / denom < 2e-2, "w4a16 stacked kernel diverges"
    got = np.asarray(quant_matmul4(x, qt.q[0], qt.s[0]), np.float32)
    ref = np.asarray(xla(x, qt.q[0], qt.s[0]), np.float32)
    assert np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) or 1.0) < 2e-2

    k_ms = _time_ms(lambda: quant_matmul_stacked4(x, qt.q, qt.s, 1))
    x_ms = _time_ms(lambda: xla(x, qt.q[1], qt.s[1]))
    print(f"int4 H={H} O={O} (1d bo={bo}, ng={ng}): kernel {k_ms:.4f} ms "
          f"vs XLA {x_ms:.4f} ms ({x_ms / k_ms:.2f}x)")
    if k_ms > x_ms * 1.02:
        raise SlowerThanXLA(f"kernel {k_ms:.4f} ms vs XLA {x_ms:.4f} ms")


def run_experts8(H: int, O: int, NE: int = 8, L: int = 2,
                 rows: int = EXPERT_ROWS) -> None:
    """w8a16 grouped expert dispatch (round 18): the per-expert stripe
    walk vs the forced-XLA dequant einsum at decode-class capacity."""
    rng = np.random.default_rng(H + O + 2)
    x = jnp.asarray(rng.standard_normal((NE, rows, H), np.float32),
                    jnp.bfloat16)
    qt = _quantized(H + O + 2, (L, NE, H, O), quantize)
    assert pick_expert_bo(rows, H, O, 2) is not None, \
        f"expert kernel must cover H={H} O={O}"

    xla = jax.jit(lambda x, q, s: jnp.einsum(
        "ech,ehf->ecf", x, q.astype(x.dtype)) * s)
    for layer in (0, L - 1):
        got = np.asarray(quant_matmul_experts_stacked(x, qt.q, qt.s, layer),
                         np.float32)
        ref = np.asarray(xla(x, qt.q[layer], qt.s[layer]), np.float32)
        err = np.max(np.abs(got - ref))
        denom = np.max(np.abs(ref)) or 1.0
        print(f"int8 experts H={H} O={O} layer={layer}: rel "
              f"{err / denom:.5f}")
        assert err / denom < 2e-2, "w8a16 expert kernel diverges"

    k_ms = _time_ms(lambda: quant_matmul_experts_stacked(x, qt.q, qt.s, 1))
    x_ms = _time_ms(lambda: xla(x, qt.q[1], qt.s[1]))
    bo = pick_expert_bo(rows, H, O, 2)
    print(f"int8 experts H={H} O={O} NE={NE} C={rows} (bo={bo}): "
          f"kernel {k_ms:.4f} ms vs XLA {x_ms:.4f} ms ({x_ms / k_ms:.2f}x)")
    if k_ms > x_ms * 1.02:
        raise SlowerThanXLA(f"kernel {k_ms:.4f} ms vs XLA {x_ms:.4f} ms")


HBM_GBPS = 819.0   # TPU v5e


def _shown_widths(H: int, O: int) -> list:
    return [bo for bo in qmm.expert_widths(O)
            if H * bo <= 2 * qmm._EXPERT_STRIPE_BYTES]


def sweep_experts8(H: int, O: int, NE: int, rows: tuple,
                   widths: tuple | None = None, touched: tuple = (),
                   label: str = "") -> None:
    """The measurement behind `pick_expert_bo`: the expert-stripe
    kernel's time at every bucket size ``rows`` at every stripe width of
    ``widths`` (None: every multiple of 128 that divides ``O``, as far
    as twice the rule's stripe limit, to show what lies past it; the one
    the rule picks is marked), beside the XLA dequant einsum and the time
    the bytes take at the chip's bandwidth. One dispatch runs the matmul
    ``REPEAT`` times over alternating layers, as a model's layer scan
    does: a lone call of half a millisecond measures the host's
    dispatch, not the kernel. A width Mosaic refuses prints what it said
    (the scoped allocation against the limit) beside the rule's own
    account of it, and the sweep goes on.

    ``touched``: for each ``t`` of it, the kernel again with only ``t``
    of the ``NE`` buckets holding rows (spread evenly over the experts,
    the rest zero, the count handed to the kernel as the dispatch hands
    it): ms, and the GB/s of the bytes that then had to be read, the
    ``t`` experts' weights and every bucket's rows in and out."""
    L, REPEAT = 2, 16
    if widths is None:
        widths = _shown_widths(H, O)
    qt = _quantized(H + O + 5, (L, NE, H, O), quantize)

    def repeated(one):
        @jax.jit
        def run(x, q, s):
            def body(i, acc):
                return acc + one(x, q, s, i % L)
            return jax.lax.fori_loop(
                0, REPEAT, body, jnp.zeros((NE, x.shape[1], O), x.dtype))
        return run

    xla = repeated(lambda x, q, s, layer: jnp.einsum(
        "ech,ehf->ecf", x, q[layer].astype(x.dtype)) * s[layer].astype(
            x.dtype))
    for C in rows:
        x = jax.random.normal(jax.random.PRNGKey(C), (NE, C, H),
                              jnp.bfloat16)
        nbytes = NE * (H * O + 4 * O + 2 * C * (H + O))
        floor_ms = nbytes / (HBM_GBPS * 1e9) * 1e3
        x_ms = _time_ms(lambda: xla(x, qt.q, qt.s)) / REPEAT
        picked = pick_expert_bo(C, H, O, 2)
        print(f"sweep {label}H={H} O={O} NE={NE} C={C}: bytes {nbytes} "
              f"floor {floor_ms:.4f} ms, XLA {x_ms:.4f} ms, the rule "
              f"picks bo={picked}")
        for bo in widths:
            account = qmm.expert_vmem_bytes(C, H, bo, 2) / 2**20
            mark = " <- the rule" if bo == picked else ""
            kern = repeated(functools.partial(quant_matmul_experts_stacked,
                                              bo=bo))
            try:
                k_ms = _time_ms(lambda: kern(x, qt.q, qt.s)) / REPEAT
            except Exception as e:  # noqa: BLE001 - Mosaic's refusal
                said = re.search(r"[Ss]coped allocation[^.]*\.\d+M[^.]*"
                                 r"\.\d+M", str(e))
                print(f"  bo={bo} grid={NE}x{O // bo}: REFUSED "
                      f"({said.group(0) if said else str(e)[-200:]}; the "
                      f"account {account:.2f}M){mark}", flush=True)
                continue
            print(f"  bo={bo} grid={NE}x{O // bo}: {k_ms:.4f} ms = "
                  f"{100 * floor_ms / k_ms:.1f}% of roofline, "
                  f"{x_ms / k_ms:.2f}x XLA (the account {account:.2f}M)"
                  f"{mark}", flush=True)
            for t in touched:
                held = np.zeros((NE,), np.int32)
                held[[j * NE // t for j in range(t)]] = C
                count = jnp.asarray(held)
                xt = x * (count > 0)[:, None, None].astype(x.dtype)
                part = repeated(
                    lambda x, q, s, layer: quant_matmul_experts_stacked(
                        x, q, s, layer, count, bo=bo))
                t_ms = _time_ms(lambda: part(xt, qt.q, qt.s)) / REPEAT
                read = t * (H * O + 4 * O) + NE * 2 * C * (H + O)
                print(f"    touched {t}/{NE}: {t_ms:.4f} ms = "
                      f"{t_ms / k_ms:.3f} of all touched without a "
                      f"count ({t / NE:.3f} of the experts), "
                      f"{read / t_ms / 1e6:.1f} GB/s of {read} bytes",
                      flush=True)
    del qt
    jax.clear_caches()


# The expert matmuls the benchmark's cells dispatch (PERF.md section 4):
# label, H, O, experts a layer holds, experts a full decode step touches
# (where not all: the cell's `moe_touched_share`, or 1 - (1 - k / E)^32;
# OLMoE's six in ten by its cell's trace, PERF.md section 6 PR 47).
CELL_SHAPES = (
    ("nemotron up", 1024, 2688, 128, 96),
    ("nemotron down", 2688, 1024, 128, 96),
    ("olmoe down", 1024, 2048, 64, 38),
    ("olmoe gate|up", 2048, 2048, 64, 38),
    ("mellum gate|up", 2304, 1792, 64, 64),
    ("mellum down", 896, 2304, 64, 64),
    ("lfm2 gate|up", 2048, 3584, 32, 6),
    ("lfm2 down", 1792, 2048, 32, 6),
    ("openpangu gate|up", 7680, 4096, 16, 5),
    ("openpangu down", 2048, 7680, 16, 5),
    ("mixtral gate|up", 4096, 28672, 8, 8),
    ("mixtral down", 14336, 4096, 8, 8),
)


def run_experts4(H: int, O: int, NE: int = 8, L: int = 2) -> None:
    """w4a16 grouped expert dispatch at the grouping quantize-time
    chooses for expert leaves — at mixtral-large's H=11520 that is
    group 256 => ng=45, the ODD group count whose half-group segment
    walk round 18 added."""
    group = _int4_group(H, True)
    assert group is not None, f"_int4_group must serve expert H={H}"
    rng = np.random.default_rng(H + O + 3)
    x = jnp.asarray(rng.standard_normal((NE, EXPERT_ROWS, H), np.float32),
                    jnp.bfloat16)
    qt = _quantized(H + O + 3, (L, NE, H, O),
                    functools.partial(quantize4, group=group))
    ng = qt.s.shape[-2]
    bo = pick_int4_bo(EXPERT_ROWS, H, O, ng, 2)
    assert bo is not None, \
        f"w4a16 expert kernel must cover H={H} O={O} ng={ng}"

    xla = jax.jit(lambda x, q, s: jnp.einsum(
        "ech,ehf->ecf", x, dequantize4(QTensor4(q=q, s=s), x.dtype)))
    for layer in (0, L - 1):
        got = np.asarray(
            quant_matmul_experts_stacked4(x, qt.q, qt.s, layer), np.float32)
        ref = np.asarray(xla(x, qt.q[layer], qt.s[layer]), np.float32)
        err = np.max(np.abs(got - ref))
        denom = np.max(np.abs(ref)) or 1.0
        print(f"int4 experts H={H} O={O} ng={ng} layer={layer}: rel "
              f"{err / denom:.5f}")
        assert err / denom < 2e-2, "w4a16 expert kernel diverges"

    k_ms = _time_ms(lambda: quant_matmul_experts_stacked4(x, qt.q, qt.s, 1))
    x_ms = _time_ms(lambda: xla(x, qt.q[1], qt.s[1]))
    print(f"int4 experts H={H} O={O} NE={NE} (bo={bo}, ng={ng}"
          f"{', odd walk' if ng % 2 else ''}): kernel {k_ms:.4f} ms vs "
          f"XLA {x_ms:.4f} ms ({x_ms / k_ms:.2f}x)")
    if k_ms > x_ms * 1.02:
        raise SlowerThanXLA(f"kernel {k_ms:.4f} ms vs XLA {x_ms:.4f} ms")


def main() -> int:
    require_tpu()
    # (H, O) per serving config's decode projections: draft-400m's
    # H=1024 trunk (the _TILE_TABLE retune rows), bench-1b's H=2048,
    # then every llama3.1-8b projection — fused qkv, wo, fused gate|up,
    # w_down and the vocabulary head.
    cases = []
    for H, O in ((1024, 2048), (1024, 4096), (2048, 2048), (2048, 11264),
                 (4096, 6144), (4096, 4096), (4096, 28672), (14336, 4096),
                 (4096, 128256)):
        cases += [(f"int8 H={H} O={O}", functools.partial(run8, H, O)),
                  (f"int4 H={H} O={O}", functools.partial(run4, H, O))]
    # MoE expert pools: bench-moe's fused wgu_e [H=1024, O=2F=5632] and
    # w_down [2816, 1024], then mixtral-large's expert scale — wgu_e
    # [4096, 23040] and w_down [11520, 4096], the int4 odd-group-count
    # walk (group 256 => ng=45).
    for H, O in ((1024, 5632), (2816, 1024), (4096, 23040),
                 (11520, 4096)):
        cases += [(f"int8 experts H={H} O={O}",
                   functools.partial(run_experts8, H, O)),
                  (f"int4 experts H={H} O={O}",
                   functools.partial(run_experts4, H, O))]
    # OLMoE-1B-7B's thin experts (NE 64, F 1024): fused gate|up
    # [2048 -> 2048] and w_down [1024 -> 2048], at the decode bucket
    # (C = 32 rows), a part-full one (8) and the prefill buckets of 256-
    # and 2048-token admissions under capacity factor 2 (64, 512).
    for H, O in ((2048, 2048), (1024, 2048)):
        for C in (8, 32, 64, 512):
            cases.append((f"int8 experts ne64 H={H} O={O} C={C}",
                          functools.partial(run_experts8, H, O, 64, 2, C)))
    # ``python tools/check_quant_kernel.py ne64``: only the cases whose
    # label holds the word. ``sweep-cells [word]``: instead of the
    # verdicts, the stripe sweep behind `pick_expert_bo` at every expert
    # matmul a cell dispatches (those whose label holds the word), every
    # candidate width x 16 / 32 / 64 / 128 rows x all experts touched and
    # the cell's share of them; ``sweep-ne64`` is its OLMoE rows at the
    # buckets a capacity dispatch would fill (8 to 512 rows).
    mode = sys.argv[1] if len(sys.argv) > 1 else ""
    if mode == "sweep-cells":
        word = sys.argv[2] if len(sys.argv) > 2 else ""
        for label, H, O, NE, t in CELL_SHAPES:
            if word in label:
                # The six widest, and what the dense search gave the
                # expert grid until PR 47.
                was = _pick_1d_bo(32, H, O, 2)
                widths = _shown_widths(H, O)[:6]
                if was not in widths:
                    widths.append(was)
                sweep_experts8(H, O, NE, (16, 32, 64, 128), tuple(widths),
                               touched=(t,) if t < NE else (),
                               label=f"[{label}, dense search {was}] ")
        return 0
    if mode == "sweep-ne64":
        for label, H, O, NE, _ in CELL_SHAPES:
            if label.startswith("olmoe"):
                sweep_experts8(H, O, NE, (8, 32, 64, 128, 256, 512),
                               label=f"[{label}] ")
        return 0
    # ``sweep-touched``: the decode bucket (C = 32) with part of the
    # experts empty, at Mixtral-8x7B's two expert shapes and OLMoE's,
    # at the stripe the program picks.
    if mode == "sweep-touched":
        for H, O, NE, touched in ((4096, 28672, 8, (2, 4, 8)),
                                  (14336, 4096, 8, (2, 4, 8)),
                                  (2048, 2048, 64, (16, 48, 64)),
                                  (1024, 2048, 64, (16, 48, 64))):
            sweep_experts8(H, O, NE, (32,),
                           (pick_expert_bo(32, H, O, 2),), touched)
        return 0
    if len(sys.argv) > 1:
        cases = [c for c in cases if sys.argv[1] in c[0]]
    failed, slower = run_cases(cases)
    print(f"quant kernels: {len(cases) - failed}/{len(cases)} compile and "
          f"match XLA, {slower} lose to XLA on time")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

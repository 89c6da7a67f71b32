"""TPU parity + timing check: the Mamba-2 decode kernel vs XLA's update.

Runs ``ops/state_pool.ssm_decode_kernel`` on the real chip against the
program XLA makes of the same step (``ssm_step`` behind a dynamic_slice,
``where(live, ...)`` and a dynamic_update_slice: what ``decode_update``
runs off the chip and for Mamba-1) at the pool of
``nemotron-3-super-120b-a12b-l22e128.chat-backlog``,
``f32[10,33,128,64,128]`` with 32 rows a step, at 32, 29 and 2 of 32
rows live, and at a small pool that tiles. CPU tests cover the math in
interpret mode (tests/test_state_pool.py); this is the Mosaic lowering
and the measurement behind ``pick_head_block``.

Every case gets one verdict line (tools/kernel_verdicts.py): ``PASS``
(compiled; ``y`` and the live rows' state within float32 rounding of
XLA's, every other row and layer bit-equal), ``FAIL`` with the reason,
and beside a pass ``loses to XLA`` where the kernel is the slower. A
time is a layer-step's: one dispatch walks every layer of the pool
``REPEAT`` times over, donated, as a decode program's layer scan does,
and the GB/s are the bytes the benchmark counts (a row's state read and
written, ``benchmark/architectures/nemotron_h.decode_step_bytes``) over
that time, for every row of the step and for the live ones.

``python tools/check_state_kernel.py sweep`` prints the kernel's time at
every head block the shape allows instead, the rule's own marked.
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

from p2p_llm_chat_tpu.ops import state_pool  # noqa: E402
from tools.kernel_verdicts import (SlowerThanXLA, require_tpu,  # noqa: E402
                                   run_cases)

HBM_GBPS = 819.0   # TPU v5e
REPEAT = 4         # walks of the pool's layers a dispatch
STEPS = 5          # dispatches a timing

# (label, pool [L, rows, H, P, N], groups, rows a step)
CELL = ("nemotron-l22e128", (10, 33, 128, 64, 128), 8, 32)
SMALL = ("small", (3, 5, 8, 8, 128), 2, 4)


def xla_update(ssm, layer, live, x, dt, A, Bm, Cm):
    """The step as XLA has it: ``decode_update``'s own lines."""
    B = x.shape[0]
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(layer, jnp.int32),) + (zero,) * 4
    S = jax.lax.dynamic_slice(ssm, at, (1, B) + ssm.shape[2:])[0]
    y, S_new = state_pool.ssm_step(S, x, dt, A, Bm, Cm)
    S_new = jnp.where(live[:, None, None, None], S_new, S)
    return y, jax.lax.dynamic_update_slice(ssm, S_new[None], at)


def _inputs(shape: tuple, groups: int, B: int, seed: int = 0):
    _, _, H, P, N = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (B, H, P), jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(ks[1], (B, H)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.5)),
            jax.random.normal(ks[3], (B, groups, N), jnp.bfloat16),
            jax.random.normal(ks[4], (B, groups, N), jnp.bfloat16))


def _pool(shape: tuple, seed: int = 1):
    return jax.jit(lambda: jax.random.normal(
        jax.random.PRNGKey(seed), shape, jnp.float32))()


def _live(B: int, n: int) -> jax.Array:
    """``n`` of ``B`` rows live, spread: the first and the last are not
    where any is not."""
    if n == B:
        return jnp.ones((B,), bool)
    at = np.linspace(1, B - 2, n).round().astype(int)
    return jnp.zeros((B,), bool).at[at].set(True)


def _walk(update):
    """A donated program: ``update`` at every layer, ``REPEAT`` times."""
    def program(ssm, live, *inp):
        def body(i, carry):
            ssm, acc = carry
            y, ssm = update(ssm, i % ssm.shape[0], live, *inp)
            return ssm, acc + y
        B, H, P = inp[0].shape
        return jax.lax.fori_loop(0, REPEAT * ssm.shape[0], body,
                                 (ssm, jnp.zeros((B, H, P), jnp.float32)))
    return jax.jit(program, donate_argnums=(0,))


def _time_ms(program, ssm, live, inp) -> tuple:
    """ms a layer-step, and the pool back (it was donated)."""
    ssm, acc = program(ssm, live, *inp)
    np.asarray(acc).ravel()[:1]
    t = time.monotonic()
    for _ in range(STEPS):
        ssm, acc = program(ssm, live, *inp)
    np.asarray(acc).ravel()[:1]
    return ((time.monotonic() - t) / (STEPS * REPEAT * ssm.shape[0]) * 1e3,
            ssm)


def _moved_bytes(shape: tuple, rows: int) -> float:
    """``rows`` rows' float32 state of one layer, read and written."""
    _, _, H, P, N = shape
    return 2.0 * rows * H * P * N * 4


def _gbps(shape: tuple, rows: int, ms: float) -> float:
    return _moved_bytes(shape, rows) / (ms * 1e-3) / 1e9


@jax.jit
def _compare(got, ref, old, y, y_ref, live, layer):
    """On the device: a 1.4 GB pool is not brought to the host a case."""
    B = live.shape[0]
    here = jnp.arange(got.shape[0]) == layer
    moved = jnp.any(got != old, axis=(2, 3, 4))              # [L, rows]
    may = here[:, None] & jnp.pad(live, (0, got.shape[1] - B))[None, :]
    lv = live[:, None, None, None]
    err = lambda a, b, m: jnp.max(jnp.where(m, jnp.abs(a - b), 0.0))
    return {"moved": jnp.sum(moved & ~may),
            "state_err": err(got[layer, :B], ref[layer, :B], lv),
            "y_err": err(y, y_ref, lv[..., 0]),
            "y_dead": jnp.max(jnp.where(lv[..., 0], 0.0, jnp.abs(y))),
            "y_max": jnp.max(jnp.abs(y_ref))}


def parity(shape: tuple, groups: int, B: int, n_live: int, **how) -> None:
    inp, live = _inputs(shape, groups, B), _live(B, n_live)
    kernel = jax.jit(functools.partial(state_pool.ssm_decode_kernel, **how))
    for layer in (0, shape[0] - 1):
        ssm = _pool(shape)
        y_ref, ref = jax.jit(xla_update)(ssm, layer, live, *inp)
        y, got = kernel(ssm, layer, live, *inp)
        r = {k: float(v) for k, v in _compare(
            got, ref, ssm, y, y_ref, live, layer).items()}
        print(f"layer {layer}: state max abs err {r['state_err']:.2e}, y "
              f"{r['y_err']:.2e} of {r['y_max']:.1f}")
        assert r["moved"] == 0, \
            f"{r['moved']:.0f} rows moved that are not live rows of the layer"
        assert r["y_dead"] == 0, "y of a row that is not live"
        assert r["state_err"] < 1e-5 and r["y_err"] < 1e-4 * r["y_max"], r


def case(label: str, shape: tuple, groups: int, B: int, n_live: int):
    def run():
        parity(shape, groups, B, n_live)
        inp, live = _inputs(shape, groups, B), _live(B, n_live)
        k_ms, ssm = _time_ms(_walk(state_pool.ssm_decode_kernel),
                             _pool(shape), live, inp)
        x_ms, _ = _time_ms(_walk(xla_update), ssm, live, inp)
        hb = state_pool.pick_head_block(*shape[2:], groups)
        print(f"{label} {n_live}/{B} live (hb={hb}): kernel {k_ms:.4f} ms "
              f"a layer-step, {_gbps(shape, B, k_ms):.0f} GB/s of the "
              f"step's rows, {_gbps(shape, n_live, k_ms):.0f} of the live "
              f"ones; XLA {x_ms:.4f} ms, {_gbps(shape, B, x_ms):.0f} GB/s "
              f"({x_ms / k_ms:.2f}x)")
        if k_ms > x_ms * 1.02:
            raise SlowerThanXLA(f"kernel {k_ms:.4f} ms vs XLA {x_ms:.4f} ms")
    return f"ssm-decode {label} {n_live}/{B}", run


def sweep(label: str, shape: tuple, groups: int, B: int,
          lives: tuple = (32, 29, 2)) -> None:
    """The kernel's time at every head block the shape allows; a block
    Mosaic refuses prints what it said, and the sweep goes on."""
    _, _, H, P, N = shape
    picked = state_pool.pick_head_block(H, P, N, groups)
    inp = _inputs(shape, groups, B)
    ssm = _pool(shape)
    for n_live in lives:
        live = _live(B, n_live)
        x_ms, ssm = _time_ms(_walk(xla_update), ssm, live, inp)
        print(f"sweep {label} {n_live}/{B} live: XLA {x_ms:.4f} ms a "
              f"layer-step; 2 x live bytes at {HBM_GBPS:.0f} GB/s "
              f"{_moved_bytes(shape, n_live) / HBM_GBPS / 1e6:.4f} ms",
              flush=True)
        for hb in reversed(state_pool.head_blocks(H, groups)):
            vmem = state_pool.ssm_kernel_vmem_bytes(hb, P, N) / 2 ** 20
            try:
                if n_live == lives[0]:
                    parity(shape, groups, B, n_live, hb=hb)
                k_ms, ssm = _time_ms(
                    _walk(functools.partial(state_pool.ssm_decode_kernel,
                                            hb=hb)), ssm, live, inp)
            except Exception as e:   # noqa: BLE001 — the sweep goes on
                print(f"  hb={hb:<3d} ({vmem:.2f} MiB): refused "
                      f"({str(e).strip()[-300:]})", flush=True)
                ssm = _pool(shape)
                continue
            print(f"  hb={hb:<3d} ({vmem:.2f} MiB): {k_ms:.4f} ms, "
                  f"{_gbps(shape, n_live, k_ms):.0f} GB/s of the live rows "
                  f"({x_ms / k_ms:.2f}x XLA)"
                  f"{'  <- the rule' if hb == picked else ''}", flush=True)


def main() -> int:
    require_tpu()
    if sys.argv[1:2] == ["sweep"]:
        sweep(*CELL)
        return 0
    cases = [case(*CELL, n) for n in (32, 29, 2)]
    cases += [case(*SMALL, n) for n in (4, 2)]
    failed, slower = run_cases(cases)
    print(f"{len(cases) - failed} of {len(cases)} cases pass, {slower} lose "
          "to XLA")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

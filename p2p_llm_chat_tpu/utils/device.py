"""The device this process computes on: one probe, one boot check, one report.

Every platform-dependent choice in the package reads :func:`on_tpu` —
which Pallas kernels dispatch (models/layers.py prefill flash,
models/quant.py w8a16/w4a16 matmuls, ops/paged_attention.py
flash-append) and whether a Pallas kernel runs compiled or in interpret
mode (models/llama.py). One cached answer means the choices cannot
disagree, and on the TPU nothing can pick interpret mode or an XLA
stand-in for a kernel the probe enabled.

Tests run on the CPU (tests/conftest.py pins ``JAX_PLATFORMS=cpu``):
there the XLA paths serve and explicitly-driven kernels interpret.
"""

from __future__ import annotations

import functools

import jax

from .chips import cpu_pinned
from .log import get_logger

log = get_logger("device")


@functools.cache
def platform() -> str:
    """Platform of JAX's default device (``tpu`` | ``cpu`` | ...),
    probed once per process — the backend cannot change after JAX
    initialises, and the answer is baked into traced programs."""
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return platform() == "tpu"


def pallas_interpret() -> bool:
    """Interpret mode for Pallas kernels the model code dispatches:
    off on the TPU (Mosaic compiles them), on everywhere else (the CPU
    test platform has no Mosaic)."""
    return not on_tpu()


def require_tpu(what: str) -> None:
    """Fail the boot of an entry point that was asked for the TPU when
    JAX came up on anything else. JAX falls back to the CPU quietly
    when the chip is missing or another process holds it; serving from
    there looks healthy and is hundreds of times slower. The one way
    onto the CPU is the operator pinning ``JAX_PLATFORMS=cpu`` in the
    environment (what the test suite does)."""
    try:
        if on_tpu():
            return
    except RuntimeError as e:
        # JAX_PLATFORMS names a backend that cannot initialise.
        raise SystemExit(f"{what} needs a TPU, but JAX found no usable "
                         f"device: {e}") from None
    if cpu_pinned():
        log.warning("%s: JAX_PLATFORMS=cpu is pinned — running on the CPU "
                    "(XLA paths; Pallas kernels interpret)", what)
        return
    raise SystemExit(
        f"{what} needs a TPU, but JAX came up on {platform()!r} "
        f"({jax.devices()}): no chip is visible or another process holds "
        "it. Set JAX_PLATFORMS=cpu to run on the CPU on purpose.")


def device_info() -> dict:
    """``{platform, device_kind, count}`` as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs)}


def bytes_in_use() -> list[int]:
    """Allocated bytes per local device (0 where the backend keeps no
    statistics — the CPU)."""
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.local_devices()]

"""Shared runner for the on-chip kernel checks (check_append_kernel.py,
check_quant_kernel.py): every case runs to the end and gets one
``VERDICT <label>: PASS|FAIL`` line, so one refused kernel does not hide
the rest."""

from __future__ import annotations

import traceback

import jax


class SlowerThanXLA(Exception):
    """Parity held; the kernel lost a timing comparison. Reported beside
    the PASS, not counted as a failure."""


def require_tpu() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU (Mosaic lowering); JAX came up on "
                         f"{dev.platform!r}")
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}")


def run_cases(cases) -> tuple[int, int]:
    """Run ``(label, fn)`` cases; returns (failed, slower) counts."""
    failed = slower = 0
    for label, fn in cases:
        try:
            fn()
            print(f"VERDICT {label}: PASS", flush=True)
        except SlowerThanXLA as e:
            slower += 1
            print(f"VERDICT {label}: PASS, loses to XLA ({e})", flush=True)
        except Exception as e:   # noqa: BLE001 — every case gets a verdict
            failed += 1
            traceback.print_exc()
            reason = str(e).strip().splitlines()[0][:200] if str(e) else ""
            print(f"VERDICT {label}: FAIL ({type(e).__name__}: {reason})",
                  flush=True)
    return failed, slower

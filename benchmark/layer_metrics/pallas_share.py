"""Kernels (ops/quant_mm.py, ops/paged_attention.py): device time inside
Pallas (Mosaic) custom calls / device busy time, from the trace, %."""


def read(obs):
    busy = obs.trace.get("busy_s")
    if not busy or "pallas_s" not in obs.trace:
        return None
    return 100.0 * obs.trace["pallas_s"] / busy

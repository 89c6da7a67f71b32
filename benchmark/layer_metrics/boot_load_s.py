"""Launcher and engine: the gauge ``serve_boot_load_seconds`` at the
window's first scrape, s: process start (as the OS records it) until the
scheduler is built: interpreter start, imports, the streamed int8 init,
the KV pool."""


def read(obs):
    return obs.counters_start.get("serve_boot_load_seconds") or None

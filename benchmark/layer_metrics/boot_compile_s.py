"""Launcher and engine: the gauge ``serve_boot_compile_seconds`` at the
window's first scrape, s: the compilation and compile-cache retrieval
JAX reported in the server process until it was ready (the program's own
``jax.monitoring`` listener): the part of load and warm-up that a warm
cache or fewer programs would shorten."""


def read(obs):
    return obs.counters_start.get("serve_boot_compile_seconds") or None

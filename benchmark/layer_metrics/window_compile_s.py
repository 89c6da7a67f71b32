"""Launcher and engine: seconds of compilation (and compile-cache
retrieval) JAX reported in the server process inside the window, s: the
window difference of ``serve_compile_seconds_total``, the running total
of the clock whose value at ready is ``serve_boot_compile_seconds``. 0
in a sound run: every program the window uses was compiled in warm-up."""


def read(obs):
    return obs.counter_delta("serve_compile_seconds_total")

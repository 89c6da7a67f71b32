"""Scheduler: share of the loop thread's host-only wall that it spent
off the CPU, %: 1 - CPU seconds / the wall seconds of the same marks
(the program reads the thread's CPU clock in one loop iteration of a
few, and keeps those marks' wall beside it:
``serve_loop_<name>_cpu_seconds_total`` /
``serve_loop_<name>_cpu_wall_seconds_total``), window differences,
summed over the phases that are Python on the host: ``admit``,
``prefill_chunk``, ``decode_dispatch``, ``stream`` and ``other``, less
their ``upload`` and ``launch`` parts (which may block inside the
runtime) and the arrival-gap wait (a wait by design). What is left has
nothing to wait for but the interpreter lock and the OS. None on a
program that reads no CPU clock."""

PHASES = ("admit", "prefill_chunk", "decode_dispatch", "stream", "other")
NOT_HOST_ONLY = ("admit_gap", "admit_upload", "admit_launch",
                 "prefill_chunk_upload", "prefill_chunk_launch",
                 "decode_dispatch_upload", "decode_dispatch_launch",
                 "stream_launch")


def read(obs):
    wall = cpu = 0.0
    for names, sign in ((PHASES, 1.0), (NOT_HOST_ONLY, -1.0)):
        for n in names:
            w = obs.counter_delta(f"serve_loop_{n}_cpu_wall_seconds_total")
            c = obs.counter_delta(f"serve_loop_{n}_cpu_seconds_total")
            if w is None or c is None:
                return None
            wall += sign * w
            cpu += sign * c
    if wall <= 0.0:
        return None
    return 100.0 * (1.0 - cpu / wall)

"""Scheduler: of the launches that feed the device (admissions, prefill
chunks, decode dispatches), the share that found the last launch's
output already arrived, %: window differences of
``serve_launch_<kind>_starved_total`` / ``serve_launch_<kind>_total``
over the three kinds. The device had nothing of the loop's left to run:
the loop's own reading of ``device_idle``, over the whole window and
with no profiler. None on a program without the counters."""

KINDS = ("admit", "prefill_chunk", "decode")


def read(obs):
    starved = [obs.counter_delta(f"serve_launch_{k}_starved_total")
               for k in KINDS]
    launches = [obs.counter_delta(f"serve_launch_{k}_total") for k in KINDS]
    if None in starved or None in launches or not sum(launches):
        return None
    return 100.0 * sum(starved) / sum(launches)

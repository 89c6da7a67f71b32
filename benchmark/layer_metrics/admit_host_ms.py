"""Scheduler: host milliseconds an admission dispatch costs before the
device has it: window differences of the loop's ``admit`` and
``prefill_chunk`` phases (self wall seconds with their parts': collect,
the arrival-gap wait, plan, build, upload, launch, and what is left of
the two; the ``readback`` and ``stream`` marked inside them are phases
of their own and not in it) over ``serve_admit_batches_total`` +
``prefill_chunks_total``. The parts themselves are on ``/metrics``
(``serve_loop_<phase>_<part>_seconds_total``). None on a program that
does not time the parts: its two totals would divide to a number that
nothing could then take apart."""


def read(obs):
    if obs.counter_delta("serve_loop_admit_launch_seconds_total") is None:
        return None
    admit = obs.counter_delta("serve_loop_admit_seconds_total")
    chunk = obs.counter_delta("serve_loop_prefill_chunk_seconds_total")
    batches = obs.counter_delta("serve_admit_batches_total")
    chunks = obs.counter_delta("prefill_chunks_total")
    if None in (admit, chunk, batches, chunks) or not batches + chunks:
        return None
    return (admit + chunk) * 1e3 / (batches + chunks)

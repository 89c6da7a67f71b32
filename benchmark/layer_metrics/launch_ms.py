"""Scheduler: what calling a compiled program costs the host, ms a
launch: window differences of the ``launch`` parts' self wall seconds,
under every phase that has one, over their marks. Argument flattening
and the runtime's enqueue, and any blocking inside the runtime. None on
a program without the parts."""

PHASES = ("admit", "prefill_chunk", "decode_dispatch", "stream")


def read(obs):
    seconds = [obs.counter_delta(f"serve_loop_{p}_launch_seconds_total")
               for p in PHASES]
    marks = [obs.counter_delta(f"serve_loop_{p}_launch_marks_total")
             for p in PHASES]
    if None in seconds or None in marks or not sum(marks):
        return None
    return sum(seconds) * 1e3 / sum(marks)

"""Scheduler: wall a decode step costs behind a whole admission, ms:
window differences of ``serve_decode_cut_admit_seconds_total`` x 1e3 /
``serve_decode_cut_admit_steps_total``: the decode dispatch intervals of
the episodes a single-shot admission or a session wake opened (the
interval it was dispatched in and the two after it; its first-token read
drains the pipeline), over the steps of the dispatch each interval
waited for. None where the class booked no step in the window: a cell
whose every prompt climbs a ladder has none."""


def read(obs):
    seconds = obs.counter_delta("serve_decode_cut_admit_seconds_total")
    steps = obs.counter_delta("serve_decode_cut_admit_steps_total")
    if seconds is None or not steps:
        return None
    return seconds * 1e3 / steps

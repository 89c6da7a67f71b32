"""Scheduler: wall a decode step costs with a real chunk queued in
front of it, ms: window differences of
``serve_decode_cut_chunk_seconds_total`` x 1e3 /
``serve_decode_cut_chunk_steps_total``: the decode dispatch intervals of
the episodes a ladder's chunk that ran its forward opened (the interval
it was dispatched in and the two after it: the one-tick pipeline delays
its device time by two dispatches), over the steps of the dispatch each
interval waited for. ``decode_step_ms`` is the same quotient over the
intervals nothing cut into, ``tick_ms`` the wall a step costs with
everything in it. None where the class booked no step in the window."""


def read(obs):
    seconds = obs.counter_delta("serve_decode_cut_chunk_seconds_total")
    steps = obs.counter_delta("serve_decode_cut_chunk_steps_total")
    if seconds is None or not steps:
        return None
    return seconds * 1e3 / steps

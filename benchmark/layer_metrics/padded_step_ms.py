"""Scheduler: wall a decode step costs behind a chunk that computes
nothing, ms: window differences of
``serve_decode_cut_padded_seconds_total`` x 1e3 /
``serve_decode_cut_padded_steps_total``: the decode dispatch intervals
of the episodes a ladder's chunk past every row's prompt opened (a
dispatch and a loop iteration, no forward: the interval it was
dispatched in and the two after it), over the steps of the dispatch each
interval waited for. Against ``decode_step_ms`` it is what skipping such
a dispatch could buy every live row. None where the class booked no step
in the window."""


def read(obs):
    seconds = obs.counter_delta("serve_decode_cut_padded_seconds_total")
    steps = obs.counter_delta("serve_decode_cut_padded_steps_total")
    if seconds is None or not steps:
        return None
    return seconds * 1e3 / steps

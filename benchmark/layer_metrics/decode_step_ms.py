"""Model programs: wall of one decode step where nothing else ran, ms:
window differences of ``serve_decode_clean_seconds_total`` x 1e3 /
``serve_decode_clean_steps_total``: the decode dispatch intervals that
no admission work cut into (the scheduler skips the interval of an
admission and, for the one-tick pipeline, the two after it), over the
steps of the dispatch each interval waited for. ``tick_ms`` is the wall
a step costs with everything else in it."""


def read(obs):
    seconds = obs.counter_delta("serve_decode_clean_seconds_total")
    steps = obs.counter_delta("serve_decode_clean_steps_total")
    if seconds is None or not steps:
        return None
    return seconds * 1e3 / steps

"""HTTP front (serve/api.py): from the loop's put of a streamed delta to
the HTTP thread's dequeue of it, ms a delta: window differences of
``serve_stream_handoff_seconds_total`` /
``serve_stream_deltas_total`` (folded in as each stream ends, so a
window holds the streams that ended in it). The thread wake-up and the
wait for the interpreter lock on the way out. None on a program without
the counters."""


def read(obs):
    seconds = obs.counter_delta("serve_stream_handoff_seconds_total")
    deltas = obs.counter_delta("serve_stream_deltas_total")
    if seconds is None or not deltas:
        return None
    return seconds * 1e3 / deltas

"""Scheduler / model programs: of the (token, expert) pairs a routed
model's prefill programs routed for real prompt positions in the window,
the share that found its capacity bucket full and was dropped (those
tokens lose that expert's contribution), %: window differences of
``serve_moe_dropped_total`` / ``serve_moe_assignments_total``. None on a
program without the counters, or where nothing was routed."""


def read(obs):
    dropped = obs.counter_delta("serve_moe_dropped_total")
    routed = obs.counter_delta("serve_moe_assignments_total")
    if dropped is None or not routed:
        return None
    return 100.0 * dropped / routed

"""Scheduler: requests a prefill admission really carried: window
differences of ``serve_admitted_total`` / ``serve_admit_batches_total``
(one batch per admission dispatch started: a single-shot program, a
chunk ladder or a session wake; warm-up's all-padding dispatches are
not counted). The programs come 1 row and at most 8 rows wide."""


def read(obs):
    admitted = obs.counter_delta("serve_admitted_total")
    batches = obs.counter_delta("serve_admit_batches_total")
    if admitted is None or not batches:
        return None
    return admitted / batches

"""Scheduler: live rows per decode step, counted at the dispatch: window
difference of ``serve_decode_row_steps_total`` (live rows x K, per decode
dispatch) / decode steps taken. ``batch_mean`` is the same ratio read
from outside (tokens the clients read over the steps)."""


def read(obs):
    row_steps = obs.counter_delta("serve_decode_row_steps_total")
    steps = obs.decode_steps()
    if row_steps is None or not steps:
        return None
    return row_steps / steps

"""State pool: of the rows whose recurrent state the window's decode
dispatches moved, the share that were live, %: window differences of
``serve_state_row_steps_live_total`` / ``serve_state_row_steps_total``.
100% when only live rows' state moves; a step that updates every slot's
row reads the batch's fill. None on a program without the counters (one
that keeps no recurrent state), or where no step ran."""


def read(obs):
    live = obs.counter_delta("serve_state_row_steps_live_total")
    moved = obs.counter_delta("serve_state_row_steps_total")
    if live is None or not moved:
        return None
    return 100.0 * live / moved

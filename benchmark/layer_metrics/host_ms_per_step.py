"""Scheduler: host work the loop thread does per decode step, ms: window
differences of (``serve_loop_seconds_total`` - the ``readback`` phase -
the ``idle`` phase) / decode steps. Everything the thread does but wait:
admission, dispatch, streaming, its own bookkeeping. The host sets the
pace when this nears ``decode_step_ms``."""


def read(obs):
    loop = obs.counter_delta("serve_loop_seconds_total")
    readback = obs.counter_delta("serve_loop_readback_seconds_total")
    idle = obs.counter_delta("serve_loop_idle_seconds_total")
    steps = obs.decode_steps()
    if loop is None or readback is None or idle is None or not steps:
        return None
    return (loop - readback - idle) * 1e3 / steps

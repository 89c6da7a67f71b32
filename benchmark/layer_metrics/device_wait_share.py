"""Scheduler: share of the loop thread's wall spent blocked on a
device-to-host read, %: window differences of
``serve_loop_readback_seconds_total`` / ``serve_loop_seconds_total``.
The loop thread's own view of ``device_idle``: while it waits the device
is what sets the pace."""


def read(obs):
    readback = obs.counter_delta("serve_loop_readback_seconds_total")
    loop = obs.counter_delta("serve_loop_seconds_total")
    if readback is None or not loop:
        return None
    return 100.0 * readback / loop

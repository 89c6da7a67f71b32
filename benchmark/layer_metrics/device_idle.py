"""Device: 1 - union of the device-operation intervals / traced stretch,
from the profiler's trace, %."""


def read(obs):
    busy, window = obs.trace.get("busy_s"), obs.trace.get("window_s")
    if not busy or not window:
        return None
    return 100.0 * (1.0 - busy / window)

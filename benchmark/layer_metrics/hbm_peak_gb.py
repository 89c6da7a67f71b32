"""Device: the server's ``memory_stats()["peak_bytes_in_use"]`` on the
fullest chip after the window, GB."""


def read(obs):
    peak = obs.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None

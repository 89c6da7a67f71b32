"""Scheduler: share of the requests due in the window that met both
limits (TTFT from due time, and TPOT), %. A failed request misses."""
from benchmark.metrics import SLO_TPOT_MS, SLO_TTFT_MS, tpot_ms, ttft_ms


def read(obs):
    counted = obs.counted()
    if not counted:
        return None
    met = 0
    for r in counted:
        a, b = ttft_ms(r), tpot_ms(r)
        if a is not None and a <= SLO_TTFT_MS and (b is None
                                                   or b <= SLO_TPOT_MS):
            met += 1
    return 100.0 * met / len(counted)

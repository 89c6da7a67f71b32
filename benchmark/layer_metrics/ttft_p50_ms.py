"""Scheduler, seen by the client: median of (first streamed token read -
time the request was due), ms. The north star's number; recorded here
and not judged while a window holds some 50 requests: between seeds it
spreads by 4-6%, and a bound of at most 0.1 needs well under 5%
(PERF.md, PR 22)."""
from benchmark.metrics import end_to_end


def read(obs):
    return end_to_end(obs)["ttft_p50_ms"]

"""Scheduler, seen by the client: 95th percentile of (first streamed
token read - time the request was due), ms: of some 50 requests the
third largest, so it is one request's wait. Recorded, not judged
(PERF.md, PR 22)."""
from benchmark.metrics import end_to_end


def read(obs):
    return end_to_end(obs)["ttft_p95_ms"]

"""Scheduler, seen by the client: 99th percentile of the gaps between
streamed chunks, all requests due in the window pooled, ms."""
from benchmark.metrics import gaps_ms, percentile


def read(obs):
    return percentile([g for r in obs.counted_ok() for g in gaps_ms(r)], 99)

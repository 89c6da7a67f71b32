"""Scheduler: median of the span ``sched.queue_wait`` (arrival ->
admission dispatch), ms."""
from benchmark.metrics import percentile


def read(obs):
    return percentile(obs.spans.get("sched.queue_wait", []), 50)

"""Model programs: median of the span ``sched.prefill`` (admission
dispatch -> first token installed, chunks and readback included), ms."""
from benchmark.metrics import percentile


def read(obs):
    return percentile(obs.spans.get("sched.prefill", []), 50)

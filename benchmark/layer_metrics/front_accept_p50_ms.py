"""HTTP front (serve/api.py): median of span ``api.accept``, ms: handler
entry until the scheduler has the request (the body's JSON, the options,
the trace header, ``submit``), timed on the HTTP thread itself. None on
a program that records no such span."""
from benchmark.metrics import percentile


def read(obs):
    return percentile(obs.spans.get("api.accept", []), 50)

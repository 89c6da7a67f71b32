"""Scheduler: median over the window's requests of the share of a
request's decode wall that lay in episodes admission work opened, %: the
span ``sched.decode.cut`` (the request's own difference of the interval
ledger's chunk, padded and admit seconds between its first token and its
release: a summed duration) over its ``sched.decode``. The scheduler
records the two at one site, in one order, for the same requests, so the
two lists pair index by index; None where their lengths differ or the
program records no ``sched.decode.cut``."""
from benchmark.metrics import percentile


def read(obs):
    cut = obs.spans.get("sched.decode.cut")
    whole = obs.spans.get("sched.decode", [])
    if not cut or len(cut) != len(whole):
        return None
    return percentile([100.0 * c / w for c, w in zip(cut, whole) if w], 50)

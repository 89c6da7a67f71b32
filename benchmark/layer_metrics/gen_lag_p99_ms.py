"""Load generator: 99th percentile of (actual send - due time), ms.
A starved generator must not be read as a fast server."""
from benchmark.metrics import percentile


def read(obs):
    return percentile([(r.send_t - r.due_t) * 1e3 for r in obs.counted()
                       if r.send_t is not None], 99)

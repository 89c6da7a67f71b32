"""HTTP front (serve/api.py): median of the client's TTFT from *send*
minus the same request's ``prompt_eval_duration`` (the scheduler's own
arrival -> first token): connection, parse, thread hand-off, NDJSON."""
from benchmark.metrics import percentile, ttft_from_send_ms


def read(obs):
    xs = []
    for r in obs.counted_ok():
        ped = r.final.get("prompt_eval_duration")
        if ped:
            xs.append(ttft_from_send_ms(r) - ped / 1e6)
    return percentile(xs, 50)

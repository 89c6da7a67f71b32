"""Scheduler: share of the prompt positions the prefill programs
computed that were padding, %: 100 x (1 - window differences of
``serve_prefill_tokens_total`` (a prompt less its prefix-store hit) /
``serve_prefill_tokens_padded_total`` (R x S per single-shot admission,
R x C per chunk)). Rows and length buckets together."""


def read(obs):
    real = obs.counter_delta("serve_prefill_tokens_total")
    padded = obs.counter_delta("serve_prefill_tokens_padded_total")
    if real is None or not padded:
        return None
    return 100.0 * (1.0 - real / padded)

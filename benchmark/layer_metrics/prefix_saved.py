"""Prefix store (serve/prefix.py): prompt tokens not recomputed
(``serve_prefix_tokens_saved_total``, window difference) / prompt tokens
of the requests due in the window (bytes + BOS), %."""


def read(obs):
    saved = obs.counter_delta("serve_prefix_tokens_saved_total")
    sent = sum(r.prompt_bytes + 1 for r in obs.counted())
    if saved is None or not sent:
        return None
    return 100.0 * saved / sent

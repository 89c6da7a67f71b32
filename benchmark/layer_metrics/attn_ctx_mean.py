"""Attention (ops/mla_attention.py, ops/paged_attention.py): the cache
rows a decode row read a step, tokens: window differences of
``serve_attn_context_tokens_total`` (the sum, over the decode row-steps,
of the row's context length at that step) /
``serve_decode_row_steps_total``. None on a program without the
counter, or where nothing decoded."""


def read(obs):
    ctx = obs.counter_delta("serve_attn_context_tokens_total")
    steps = obs.counter_delta("serve_decode_row_steps_total")
    if ctx is None or not steps:
        return None
    return ctx / steps

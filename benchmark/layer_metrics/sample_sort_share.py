"""Model programs: of the window's decode dispatches, the share that
carried a live row with a temperature above 0, %: window differences of
``serve_decode_sort_dispatches_total`` / ``serve_decode_ticks_total``.
Only such a dispatch runs the sampler's candidate sort (``lax.top_k``
over the vocabulary, the warp and the draw, under one ``lax.cond`` in
``models/sampling.sample_batched``); a step of greedy rows takes the
argmax alone. 0 under greedy traffic, which is every cell's today: a
reading above 0 there says the predicate saw a stale or padded row. An
upper bound where dispatches are fused (a sampling row may stop before
the last step of its scan). None on a program without the counter (a
commit before PR 52), or where no decode dispatch ran."""


def read(obs):
    sorts = obs.counter_delta("serve_decode_sort_dispatches_total")
    ticks = obs.counter_delta("serve_decode_ticks_total")
    if sorts is None or not ticks:
        return None
    return 100.0 * sorts / ticks

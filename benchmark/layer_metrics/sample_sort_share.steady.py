"""Model programs: ``sample_sort_share`` in the steady cells, where the
decode step's time shows as ``itl_p50_ms`` (a per-layer metric names one
end-to-end metric, so the quantity has a name a kind of cell): of the
window's decode dispatches, the share that carried a live row with a
temperature above 0, %: window differences of
``serve_decode_sort_dispatches_total`` / ``serve_decode_ticks_total``.
0 under greedy traffic; sample_sort_share.py says what a dispatch that
is counted pays for. None on a program without the counter, or where no
decode dispatch ran."""


def read(obs):
    sorts = obs.counter_delta("serve_decode_sort_dispatches_total")
    ticks = obs.counter_delta("serve_decode_ticks_total")
    if sorts is None or not ticks:
        return None
    return 100.0 * sorts / ticks

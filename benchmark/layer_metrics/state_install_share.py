"""State pool: of the recurrent-state bytes the window moved, the share
that admissions wrote into the pool, %. Every entry of an admission, a
dummy too, writes one whole row (state and window of every layer, from a
carry that a prefix hit seeded from the entry's snapshot), so what was
installed is the window difference of ``serve_admit_rows_padded_total``
x a row's bytes (the gauge ``serve_state_pool_bytes`` over the pool's
rows: the stack's ``SERVE_SLOTS`` and the garbage row), over it plus
``serve_state_bytes_total`` (what the decode steps read and wrote). A
row's state costs the same to install at every prompt length: the share
grows with the admissions a second, not with their tokens. None on a
program that keeps no recurrent state. The window ends at the last 2 Hz
sample taken inside it, where there is one (``state_bw_util`` says why)."""


def read(obs):
    inside = [c for t, c in obs.samples if obs.lo < t <= obs.hi]
    start, end = obs.counters_start, (inside or [obs.counters_end])[-1]
    names = ("serve_admit_rows_padded_total", "serve_state_bytes_total")
    slots = obs.cell.config.get("stack", {}).get("SERVE_SLOTS")
    if (any(n not in c for n in names for c in (start, end))
            or not end.get("serve_state_pool_bytes") or not slots):
        return None
    entries, moved = (end[n] - start[n] for n in names)
    installed = entries * end["serve_state_pool_bytes"] / (int(slots) + 1)
    if not installed + moved:
        return None
    return 100.0 * installed / (installed + moved)

"""Scheduler: share of the window's booked decode steps that fell in
episodes opened by a ladder's chunk past every row's prompt (no forward), %:
window differences of ``serve_decode_cut_padded_steps_total`` over the
steps of all four classes of the scheduler's interval ledger
(``serve_decode_clean_steps_total`` and the three
``serve_decode_cut_*_steps_total``). A booked interval belongs to
exactly one class, so the four shares add up to 100 and the sum of
share x step wall over the classes is the wall a booked step cost."""

_CLASSES = ("serve_decode_clean_steps_total",
            "serve_decode_cut_chunk_steps_total",
            "serve_decode_cut_padded_steps_total",
            "serve_decode_cut_admit_steps_total")


def read(obs):
    steps = {n: obs.counter_delta(n) for n in _CLASSES}
    if None in steps.values() or not sum(steps.values()):
        return None
    return (100.0 * steps["serve_decode_cut_padded_steps_total"]
            / sum(steps.values()))

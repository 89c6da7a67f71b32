"""State pool: the share of the chip's memory bandwidth that recurrent
state took, over the whole window, %: the window difference of
``serve_state_bytes_total`` (host arithmetic at each decode dispatch: the
rows whose state the program moves x Mamba layers x (state + window)
bytes, read and written, x fused steps) over ``window_s x chips x
peaks["hbm_bytes_per_s"]``, ``decode_bw_util_family``'s denominator. The
whole window is in the denominator, admissions and idle gaps too, so no
chip can read above 100%: it is the FLOOR of the state kernel's own
roofline share, which divides by the kernel's time alone (the reducer
does not sum Mosaic calls by name yet). None on a program without the
counter (one that keeps no recurrent state).

The window is the one the counters saw, as ``loop_weight_share``'s is: a
traced run's closing scrape waits for ``stop_trace`` and then holds the
drain behind the window, whose steps would be counted against the
window's seconds. So the counters' end is the last 2 Hz sample taken
inside the window, where there is one, and the seconds run to that
moment."""

COUNTER = "serve_state_bytes_total"


def read(obs):
    bw = obs.peaks.get("hbm_bytes_per_s")
    inside = [(t, c) for t, c in obs.samples if obs.lo < t <= obs.hi]
    t, end = inside[-1] if inside else (obs.hi, obs.counters_end)
    start, seconds = obs.counters_start, t - obs.lo
    if COUNTER not in start or COUNTER not in end or not bw or seconds <= 0:
        return None
    return (100.0 * (end[COUNTER] - start[COUNTER])
            / (seconds * obs.cell.chips * bw))

"""Scheduler: of the loop's iterations in the window, the share in which
a request waited for pages while a row stood free, %: window differences
of ``serve_page_starved_iterations_total`` /
``serve_loop_iterations_total``. How much of the time the page pool and
not the rows held the batch: 0 in a cell provisioned for callers x the
longest request, near 100 where the pool admits fewer rows than there are
callers. None on a program without the counter. The window ends at the
last 2 Hz sample taken inside it, where there is one: a traced run's
closing scrape waits for ``stop_trace`` and then holds the drain, in
which nobody waits (``loop_weight_share`` says more)."""


def read(obs):
    inside = [c for t, c in obs.samples if obs.lo < t <= obs.hi]
    start, end = obs.counters_start, (inside or [obs.counters_end])[-1]
    names = ("serve_page_starved_iterations_total",
             "serve_loop_iterations_total")
    if any(n not in c for n in names for c in (start, end)):
        return None
    starved, total = (end[n] - start[n] for n in names)
    if not total:
        return None
    return 100.0 * starved / total

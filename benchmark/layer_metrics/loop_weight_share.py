"""Model programs: the share of a decode step's memory traffic that is
the looped stack's weights, read once a PASS, %: the window difference of
``serve_loop_weight_bytes_total`` (host arithmetic at each decode
dispatch: ``ut_steps`` passes x the stack's stored bytes x fused steps)
over the architecture file's ``decode_step_bytes(cfg, rows, context)`` x
the window's decode steps. The stack does not stay on the chip between
passes, so a model a third of Mistral's size re-reads more weight bytes a
step than Mistral reads; what is left of the step is the pages of its 192
cache layers (``page_step_share``), the head and the embeddings. None on
a program without the counter (a model walked once) or an architecture
file without the function.

The window is the one the counters saw. A traced run's closing scrape
comes when ``stop_trace`` lets the monitor go, which in this cell is 77 s
past the middle of the window (PERF.md section 6, PR 53): the counters
then hold the drain behind the window, whose steps carry few rows, while
the rows a step holds are read from the window's records, and the share
came out 76.9% where the window's own arithmetic gives 64.5%. So the
counters' end is the last 2 Hz sample taken inside the window, and the
records are read up to that moment."""
import dataclasses

from benchmark import manifest

COUNTER = "serve_loop_weight_bytes_total"


def read(obs):
    share = manifest.load_reader(obs.cell.root, "window_step_share")
    inside = [(t, c) for t, c in obs.samples if obs.lo < t <= obs.hi]
    if inside:
        t, counters = inside[-1]
        obs = dataclasses.replace(obs, window_s=t - obs.ramp_s,
                                  counters_end=counters)
    return share(obs, COUNTER)

"""Kernels: the share of a decode step's memory traffic that is the
full-attention layers' pages, %: the window difference of
``serve_page_kv_bytes_total`` (host arithmetic at each decode dispatch:
live rows x context x bytes a token x the page layers, each read by its
own layer alone, x fused steps) over the architecture file's
``decode_step_bytes(cfg, rows, context)`` x the window's decode steps.
A model whose window layers keep rings holds pages for its full layers
only: four of sixteen here, which at 5.8 K of context still outweigh the
twelve rings (``window_step_share``). None on a program without the
counter or an architecture file without the function."""
from benchmark import manifest

COUNTER = "serve_page_kv_bytes_total"


def read(obs):
    share = manifest.load_reader(obs.cell.root, "window_step_share")
    return share(obs, COUNTER)

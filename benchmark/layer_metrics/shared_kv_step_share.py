"""Kernels: the share of a decode step's memory traffic that is the
one page layer every cross layer reads, %: the window difference of
``serve_shared_kv_bytes_total`` (host arithmetic at each decode
dispatch: live rows x context x bytes a position x the layers that read
the pool, the full layer and the cross layers above it, x fused steps)
over the architecture file's ``decode_step_bytes(cfg, rows, context)`` x
the window's decode steps: eight readers of one pool, about 23% at the
cell's contexts. None on a program without the counter or an
architecture file without the function."""
from benchmark import manifest

COUNTER = "serve_shared_kv_bytes_total"


def read(obs):
    share = manifest.load_reader(obs.cell.root, "window_step_share")
    return share(obs, COUNTER)

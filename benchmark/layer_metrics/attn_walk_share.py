"""Kernels (ops/paged_attention.py): of the (row, chunk) programs the
flash-append kernel's grid held in the window's decode dispatches (rows
x chunks of the attention window, every step), the share whose chunk
started inside its row's context, %: window differences of
``serve_attn_chunks_walked_total`` / ``serve_attn_chunks_total``. The
kernel fetches and folds those chunks and no others, so this is the
share of the window's walk a decode step still pays for. None on a
program without the counters (a commit before they existed), or where
no dispatch ran the kernel."""


def read(obs):
    walked = obs.counter_delta("serve_attn_chunks_walked_total")
    total = obs.counter_delta("serve_attn_chunks_total")
    if walked is None or not total:
        return None
    return 100.0 * walked / total

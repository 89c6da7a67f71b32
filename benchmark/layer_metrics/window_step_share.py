"""State pool: the share of a decode step's memory traffic that is the
window layers' rings, %: the window difference of
``serve_window_bytes_total`` (host arithmetic at each decode dispatch:
live rows x window layers x the positions a row's ring holds, its length
and at most ``sliding_window``, x bytes a position x fused steps) over
the architecture file's ``decode_step_bytes(cfg, rows, context)`` x the
window's decode steps. About 5% when the window is honoured at the
cell's contexts (1.5-3.6 K against 512), near 20% when a window layer
reads its whole context. None on a program without the counter (one
that keeps no ring) or an architecture file without the function."""
from benchmark import manifest

COUNTER = "serve_window_bytes_total"


def read(obs, counter: str = COUNTER):
    moved = obs.counter_delta(counter)
    steps = obs.decode_steps()
    ok = obs.counted_ok()
    if moved is None or not steps or not ok:
        return None
    cfg = obs.cell.config
    arch = manifest.load_architecture(
        obs.cell.root, cfg.get("architecture", manifest.DEFAULT_ARCHITECTURE))
    step_bytes = getattr(arch, "decode_step_bytes", None)
    if step_bytes is None:
        return None
    rows = max(1.0, obs.tokens_in_window() / steps)
    # A row's mean context over its life: its prompt plus half its output.
    ctx = sum(r.prompt_bytes + 1 + r.tokens / 2 for r in ok) / len(ok)
    return 100.0 * moved / (step_bytes(cfg, rows, ctx) * steps)

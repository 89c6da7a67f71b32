"""State pool: the share of a decode step's memory traffic that is
recurrent state, %: the window difference of ``serve_state_bytes_total``
(host arithmetic at each decode dispatch: the rows whose state the
program moves x Mamba layers x (state + window) bytes, read and written,
x fused steps) over the architecture file's ``decode_step_bytes(cfg,
rows, context)`` x the window's decode steps. A step that moved only its
live rows' state reads the widths' own share (27% at 32 rows of the
22-layer cut); one that moves every slot's reads higher at a part-full
batch. None on a program without the counter (one that keeps no
recurrent state) or an architecture file without the function."""
from benchmark import manifest


def read(obs):
    moved = obs.counter_delta("serve_state_bytes_total")
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

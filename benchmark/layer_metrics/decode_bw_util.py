"""Model programs: bytes the decode steps of the window had to read
(roofline.decode_step_bytes at the mean live rows and mean context) x
steps / (window seconds x the cell's chips x one chip's peak bytes/s),
%: a model sharded over four chips is read by four memory systems. An
end-to-end utilisation of the memory system by decode alone; not a
kernel's roofline share."""
from benchmark import roofline


def read(obs):
    steps = obs.decode_steps()
    bw = obs.peaks.get("hbm_bytes_per_s")
    if not steps or not bw:
        return None
    rows = max(1.0, obs.tokens_in_window() / steps)
    ok = obs.counted_ok()
    if not ok:
        return None
    # A row's mean context over its life: its prompt plus half its output.
    ctx = sum(r.prompt_bytes + 1 + r.tokens / 2 for r in ok) / len(ok)
    per_step = roofline.decode_step_bytes(obs.cell.config, rows=rows,
                                          context=ctx)
    return 100.0 * per_step * steps / (obs.window_s * obs.cell.chips * bw)

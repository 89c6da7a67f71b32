"""Model programs: ``decode_bw_util``'s expression for a family whose
step benchmark/roofline.py cannot describe (latent attention, a shared
expert, a dense first layer, a share of the routed experts): the byte
count is the architecture file's own ``decode_step_bytes(cfg, rows,
context)``. An end-to-end utilisation of the memory system by decode
alone, %; not a kernel's roofline share. None where the architecture
file has no such function."""
from benchmark import manifest


def read(obs):
    steps = obs.decode_steps()
    bw = obs.peaks.get("hbm_bytes_per_s")
    ok = obs.counted_ok()
    if not steps or not bw or not ok:
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
    return (100.0 * step_bytes(cfg, rows, ctx) * steps
            / (obs.window_s * obs.cell.chips * bw))

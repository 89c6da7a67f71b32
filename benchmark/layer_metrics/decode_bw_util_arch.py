"""Model programs: ``decode_bw_util``'s expression for a family whose
published key names benchmark/roofline.py does not read. The
architecture file's optional ``roofline_config(cfg)`` hands the
configuration over under the names roofline.py reads (OLMoE:
``num_experts`` -> ``num_local_experts``); the byte count stays
``roofline.decode_step_bytes``. Without that function the configuration
is read as it is, which is ``decode_bw_util`` itself. An end-to-end
utilisation of the memory system by decode alone, %; not a kernel's
roofline share."""
from benchmark import manifest, roofline


def read(obs):
    steps = obs.decode_steps()
    bw = obs.peaks.get("hbm_bytes_per_s")
    ok = obs.counted_ok()
    if not steps or not bw or not ok:
        return None
    cfg = obs.cell.config
    arch = manifest.load_architecture(
        obs.cell.root, cfg.get("architecture", manifest.DEFAULT_ARCHITECTURE))
    translate = getattr(arch, "roofline_config", None)
    if translate is not None:
        cfg = translate(cfg)
    rows = max(1.0, obs.tokens_in_window() / steps)
    # A row's mean context over its life: its prompt plus half its output.
    ctx = sum(r.prompt_bytes + 1 + r.tokens / 2 for r in ok) / len(ok)
    per_step = roofline.decode_step_bytes(cfg, rows=rows, context=ctx)
    return 100.0 * per_step * steps / (obs.window_s * obs.cell.chips * bw)

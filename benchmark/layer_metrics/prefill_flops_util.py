"""Model programs: the operations the window's prompt positions required
over what the chip could have done in the window, %: the architecture
file's ``prefill_flops(cfg, tokens, context_pairs)`` over the window
differences of ``serve_prefill_tokens_total`` (prompt positions that had
to be computed) and ``serve_prefill_context_pairs_total`` (the (token,
context) pairs they attend causally), / (window x chips x the bf16
peak). An end-to-end utilisation: padding, recomputation and decode's
own operations are not in the numerator, so it cannot over-read; not a
kernel's roofline share. None on a program without the pair counter or
an architecture file without the function."""
from benchmark import manifest


def read(obs):
    tokens = obs.counter_delta("serve_prefill_tokens_total")
    pairs = obs.counter_delta("serve_prefill_context_pairs_total")
    peak = obs.peaks.get("bf16_flops_per_s")
    if tokens is None or pairs is None or not peak or not obs.window_s:
        return None
    cfg = obs.cell.config
    arch = manifest.load_architecture(
        obs.cell.root, cfg.get("architecture", manifest.DEFAULT_ARCHITECTURE))
    flops = getattr(arch, "prefill_flops", None)
    if flops is None:
        return None
    return (100.0 * flops(cfg, tokens, pairs)
            / (obs.window_s * obs.cell.chips * peak))

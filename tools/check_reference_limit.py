"""The readings a configuration's reference limit is set from, on the chip
at the published widths (PERF.md section 6 has OLMoE's, PR 26).

Boots what a benchmark cell's child boots (benchmark/serve_cell.py: the
configuration file, its stack, random weights, no warm-up and no HTTP),
then for each sample seed drives the system as the cell's reference check
does and compares it with

- the family's plain reference (``sound``: the run a limit must pass),
- the same reference as a WRONG model, each of which a limit must fail:
  ``renormalised`` (``norm_topk_prob`` flipped), ``no_q_norm`` (``q_norm``
  of ones), and ``int4_weights`` (every projection and expert matrix
  rounded to 4 bits a column, the precision below the int8 the stack
  states); or the architecture file's own list (``wrong_models``: the
  openPangu family's softmax router, scaling factor 1, no ``n_kva``, no
  post-norms, RoPE over the nope part, the absorbed form without
  ``Wuv``, int4 weights; the Nemotron-H family's bfloat16 state, no ``D``
  skip, no convolution bias, norm before gate, no selection bias, gated
  experts, rotary applied, int4 weights, read through its own
  ``compare``, which also holds the recurrent state to a limit).

    python tools/check_reference_limit.py benchmark/configs/<name>.json \
        --seeds 53,1,2 --wrong-seeds 53,1,2 --rest-wrong bf16_state

Prints one JSON line a reading and writes them all to
``chiprun_out/reference_limit.<name>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def fake_quant(w, bits: int):
    """``w`` [in, out] rounded to ``bits`` signed bits a column (absmax
    scale), back in float32: the program's per-channel scheme."""
    import jax.numpy as jnp
    top = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / top
    return jnp.round(w / jnp.where(scale > 0, scale, 1.0)) * scale


def wrong_models(cfg: dict, weights, arch=None) -> dict:
    """name -> (cfg, weights) of each wrong model this configuration can
    have: the architecture file's own ``wrong_models(cfg, weights)``
    where it has one (a family whose layer this file does not know),
    else the Mistral and OLMoE families' below."""
    import jax.numpy as jnp
    if arch is not None and hasattr(arch, "wrong_models"):
        return arch.wrong_models(cfg, weights)
    out = {}
    if "norm_topk_prob" in cfg:
        out["renormalised"] = (
            {**cfg, "norm_topk_prob": not cfg["norm_topk_prob"]}, weights)

    def layer_with(change):
        return weights._replace(layer=lambda l: change(weights.layer(l)))

    if cfg.get("model_type") == "olmoe":
        out["no_q_norm"] = (cfg, layer_with(
            lambda w: {**w, "q_norm": jnp.ones_like(w["q_norm"])}))
    mats = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    q4 = layer_with(lambda w: {k: fake_quant(v, 4) if k in mats else v
                               for k, v in w.items()})
    if weights.expert is not None:
        q4 = q4._replace(expert=lambda l, e: tuple(
            fake_quant(m, 4) for m in weights.expert(l, e)))
    out["int4_weights"] = (cfg, q4)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_file")
    ap.add_argument("--seeds", default="53",
                    help="sample seeds of the sound reading (53 is the "
                         "one a run checks, benchmark/run.py)")
    ap.add_argument("--wrong-seeds", default="53")
    ap.add_argument("--rest-wrong", default="",
                    help="the wrong models read on the wrong seeds behind "
                         "the first (comma-separated; default: all): more "
                         "seeds for the reading nearest a limit")
    args = ap.parse_args()
    with open(args.config_file) as f:
        cfg = json.load(f)
    os.environ.update(cfg.get("stack", {}))
    os.environ.update(SERVE_BACKEND="tpu", MODEL_CONFIG=cfg["name"],
                      SERVE_WARMUP="0")

    import jax.numpy as jnp
    import numpy as np
    from benchmark import reference, serve_cell
    from p2p_llm_chat_tpu.serve import engine

    captured: dict = {}
    serve_cell.install(cfg, captured)
    backend = engine.build_engine_from_env()
    sched = backend.scheduler
    arch = serve_cell.architecture(cfg)
    weights = arch.engine_weights(sched)
    drive = getattr(arch, "system_logits", serve_cell.system_logits)
    sound = [int(s) for s in args.seeds.split(",")]
    wrong = [int(s) for s in args.wrong_seeds.split(",")]
    rest = set(filter(None, args.rest_wrong.split(",")))
    P, D = serve_cell.REF_PREFILL, serve_cell.REF_DECODE
    readings = []

    def say(**reading) -> None:
        readings.append(reading)
        print(json.dumps(reading), flush=True)

    for seed in dict.fromkeys(sound + wrong):
        tokens = jnp.asarray(np.random.default_rng(seed).integers(
            0, sched.config.vocab_size, size=(serve_cell.REF_SEQS, P + D)),
            jnp.int32)
        system = drive(sched, tokens, P)
        if seed in sound:
            ref, facts = arch.forward(cfg, tokens, weights)
            say(model="sound", seed=seed, **arch.compare(
                system, ref, {**facts, "n_prefill": P}, cfg))
        if seed in wrong:
            for name, (wcfg, w) in wrong_models(cfg, weights,
                                                arch).items():
                if seed != wrong[0] and rest and name not in rest:
                    continue
                ref, wfacts = arch.forward(wcfg, tokens, w)
                if isinstance(system, tuple):
                    # A family whose check reads more than logits (the
                    # hybrid family's state): its own verdict, whole.
                    got = arch.compare(system, ref,
                                       {**wfacts, "n_prefill": P}, wcfg)
                    say(model=name, seed=seed, **{
                        k: v for k, v in got.items() if k != "tolerance"})
                    continue
                got = reference.compare(system, ref, routed=True)
                say(model=name, seed=seed, median=got["median"],
                    p90=got["p90"], max=got["max"])
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"reference_limit.{cfg['name']}.json"),
              "w") as f:
        json.dump(readings, f, indent=1)
    backend.stop()


if __name__ == "__main__":
    main()

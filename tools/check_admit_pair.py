"""What a second request costs an admission dispatch, on the chip at a
configuration's published widths (PERF.md section 6, PR 39: the table
the admission widths were set from).

Boots what a benchmark cell's child boots (benchmark/serve_cell.py: the
configuration file, its stack, random weights, no warm-up and no HTTP),
caches the chat traffic's 88-token head as a prefix, then times the
admission programs behind it at 1 row and at 2 rows, each on real
prompts (random ids, the lengths below) into rows of the live pool:

- the single-shot splice of the 256 bucket;
- the two chunks of the 512 bucket's ladder (first, final);
- the four chunks of the 1,024 bucket's ladder (first, mid, mid, final).

The loop thread sits in its idle wait meanwhile (nothing is submitted),
so the programs run here, one at a time, each timed on the host's clock
from its launch to ``block_until_ready`` on what it returned.

    python tools/check_admit_pair.py benchmark/configs/<name>.json

Prints one JSON line a program and writes them all to
``chiprun_out/admit_pair.<name>.json``. Where the model's prefills count
the rows their experts multiplied (a dropless Mixtral-family model, and
the held-range and hybrid families: ``serve_moe_prefill_rows_total``),
a dispatch that ends an admission also reads ``moe_pairs`` and
``moe_rows`` from the counts behind its first tokens (a ladder's are
summed over its chunks; of a held range the pairs are those routed to
an expert held here).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# Suffix lengths a row, by bucket: the first is a 1-row dispatch's, both
# are a 2-row dispatch's. The 256 bucket's are the chat mixes' median
# body; the longer buckets' second row ends in an earlier chunk, as a
# shorter partner's does.
LENGTHS = {256: (210, 180), 512: (470, 230), 1024: (900, 600)}
WARM, REPS = 2, 8


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_file")
    args = ap.parse_args()
    with open(args.config_file) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "chat-backlog.json")) as f:
        head = json.load(f)["prompt"]["head"]
    os.environ.update(cfg.get("stack", {}))
    os.environ.update(SERVE_BACKEND="tpu", MODEL_CONFIG=cfg["name"],
                      SERVE_WARMUP="0")

    import jax
    import numpy as np
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.serve import engine
    from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                                GenerateRequest)
    from p2p_llm_chat_tpu.serve.scheduler import _Slot

    serve_cell.install(cfg, {})
    backend = engine.build_engine_from_env()
    sched = backend.scheduler
    P = sched.register_prefix(head)
    entry = next(e for e in sched._prefix.snapshot() if e.length == P)
    rng = np.random.default_rng(39)
    C = sched.prefill_chunk
    readings = []

    def slots(lengths) -> list:
        out = []
        for i, n in enumerate(lengths):
            s = _Slot(req=GenerateRequest(
                prompt="", options=GenerateOptions(max_tokens=64,
                                                   temperature=0.0)),
                      stats=None, out_q=None, seed=i)
            s.prompt_ids = list(entry.ids) + rng.integers(
                3, sched.config.vocab_size, size=n).tolist()
            s.max_new, s.prefix = 64, entry
            assert sched._try_reserve(s)
            out.append(s)
        return out

    def timed(fn):
        """(milliseconds until what ``fn`` returned and the pool it left
        are ready, what it returned)."""
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready((out, sched._cache))
        return (time.perf_counter() - t0) * 1e3, out

    def single_shot(packed):
        (toks, sched._cache, sched._keys, sched._next_dev, sched._temps_dev,
         sched._top_ks_dev, sched._top_ps_dev, sched._ring_dev,
         sched._rps_dev) = sched._admit_prefix_j(
            sched._params, entry.k, entry.v, entry.state, packed,
            sched._cache, sched._keys, sched._next_dev, sched._temps_dev,
            sched._top_ks_dev, sched._top_ps_dev, sched._ring_dev,
            sched._rps_dev)
        return toks

    for S, lengths in LENGTHS.items():
        for R in (1, 2):
            chunk = slots(lengths[:R])
            packed = sched._admit_upload(
                sched._admit_host_arrays(chunk, list(range(R)), S, R, entry),
                live=False)
            jax.block_until_ready(packed)
            times: dict = {}
            toks = None
            for rep in range(WARM + REPS):
                if S <= C:
                    ms = {}
                    ms["splice"], toks = timed(lambda: single_shot(packed))
                else:
                    ms, kv, logits = {}, None, None
                    for off in range(0, S, C):
                        kind = ("first" if off == 0 else
                                "final" if off + C == S else f"mid{off // C}")
                        ms[kind], (kv, logits, toks) = timed(
                            lambda: sched._dispatch_prefill_chunk(
                                P, S, off, C, packed, kv, logits, entry))
                if rep >= WARM:
                    for k, v in ms.items():
                        times.setdefault(k, []).append(v)
            for s in chunk:
                sched._alloc.free(s.pages)
            for kind, v in times.items():
                reading = {"config": cfg["name"], "S": S, "R": R,
                           "program": kind, "lengths": list(lengths[:R]),
                           "ms_median": statistics.median(v),
                           "ms_min": min(v), "ms_max": max(v),
                           "device": jax.devices()[0].device_kind}
                names = sched._moe_prefill
                if kind in ("splice", "final") and "rows" in names:
                    n = dict(zip(names,
                                 np.asarray(toks)[-len(names):].tolist()))
                    reading.update(moe_pairs=n["assigned"],
                                   moe_rows=n["rows"])
                readings.append(reading)
                print(json.dumps(reading), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"admit_pair.{cfg['name']}.json"), "w") as f:
        json.dump(readings, f, indent=1)
    backend.stop()


if __name__ == "__main__":
    main()

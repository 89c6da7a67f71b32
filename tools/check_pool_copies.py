"""Do the served programs update their pools in place? Reads the
optimised HLO the TPU compiler wrote for a run (``--xla_dump_to``) and
lists every instruction whose result has the shape of a whole pool
array (the page pool's K/V and scale arrays, the state pool's state and
window arrays), by program and opcode. In place means: the only such
instructions are parameters, tuples and their elements, bitcasts, the
while loops that carry the pool, and dynamic-update-slice or scatter
(fusions); a ``copy`` (or a plain loop fusion) of that shape is a whole-
pool copy, 1.4 GB a step for the benchmark's state pool.

One chip call does both (the dump can be gigabytes: it stays in /tmp on
the machine with the chip, only the summary comes back):

    XLA_FLAGS="--xla_dump_to=/tmp/hlo --xla_dump_hlo_as_text \
      --xla_dump_hlo_module_re=jit_(decode|prefill|kv|state).*" \
      python3 benchmark/run.py --workload <cell> --seed 7 --seconds 20 \
      --trace 0 && python tools/check_pool_copies.py /tmp/hlo \
      benchmark/configs/<name>.json

Prints one JSON line a program kind and writes
``chiprun_out/pool_copies.<name>.json``.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

IN_PLACE = {"parameter", "tuple", "get-tuple-element", "bitcast", "while",
            "dynamic-update-slice", "scatter", "conditional", "call",
            "optimization-barrier"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[a-z0-9]+\[[^=]*?)"
                    r"\s([a-z\-]+)\(")


def pool_shapes(cfg: dict) -> dict:
    """HLO shape text -> what it is, for the configuration's stack."""
    from benchmark import serve_cell
    c = serve_cell.model_config(cfg)
    stack = cfg["stack"]
    slots, pages = int(stack["SERVE_SLOTS"]), int(stack["SERVE_PAGES"])
    ps = int(stack["SERVE_PAGE_SIZE"])
    L, hk = c.cache_layers, c.cache_kv_heads
    kv = "s8" if stack.get("SERVE_KV_QUANT") == "int8" else "bf16"
    out = {f"{kv}[{L},{pages},{ps},{hk},{c.cache_k_dim}]": "pages k/v",
           f"f32[{L},{pages},{hk},{-(-ps // 128) * 128}]": "page scales"}
    if c.ssm_layers:
        state = ",".join(map(str, c.ssm_state_shape))
        out[f"f32[{c.ssm_layers},{slots + 1},{state}]"] = "state"
    if c.conv_layers:       # a Mamba layer's, or a short convolution's
        out[f"bf16[{c.conv_layers},{slots + 1},{c.conv_kernel - 1},"
            f"{c.conv_dim}]"] = "window"
    if c.window_layers:
        lead = f"{c.window_layers},{slots + 1}"
        # ops/state_pool.StatePool's layout: the head before the position.
        out[f"{kv}[{lead},{c.cache_kv_heads},{c.sliding_window},"
            f"{c.cache_k_dim}]"] = "ring k/v"
        out[f"f32[{lead},{c.cache_kv_heads},{c.sliding_window}]"] = \
            "ring scales"
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


def computations(text: str) -> dict:
    """computation name -> its instruction lines."""
    out, current = {}, None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line.strip())
            current = m.group(1) if m else None
            if current:
                out[current] = []
        elif current:
            out[current].append(line)
    return out


def scan(text: str, shapes: dict) -> collections.Counter:
    """(what, opcode) -> count over one module's text, over the
    instructions that run (a fused computation's own instructions do
    not: the fusion that calls it does). A fusion counts as an in-place
    update (``:dus``) when the computation it calls ends in a
    dynamic-update-slice or a scatter."""
    found = collections.Counter()
    comps = computations(text)
    roots, fused = {}, set()
    for name, lines in comps.items():
        for line in lines:
            if line.lstrip().startswith("ROOT"):
                m = _INSTR.match(line)
                if m:
                    roots[name] = m.group(3)
            if " fusion(" in line:
                called = re.search(r"calls=%?([\w.\-]+)", line)
                if called:
                    fused.add(called.group(1))
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            shape = m.group(2).split("{")[0].strip()
            what = shapes.get(shape)
            if what is None:
                continue
            op = m.group(3)
            if op == "fusion":
                kind = re.search(r"kind=(k\w+)", line)
                called = re.search(r"calls=%?([\w.\-]+)", line)
                root = roots.get(called.group(1), "?") if called else "?"
                op = f"fusion:{kind.group(1) if kind else '?'}:" + (
                    "dus" if root in ("dynamic-update-slice", "scatter")
                    else "root=" + root)
            found[(what, op)] += 1
    return found


def main() -> None:
    dump, config_file = sys.argv[1], sys.argv[2]
    with open(config_file) as f:
        cfg = json.load(f)
    shapes = pool_shapes(cfg)
    by_program: dict = {}
    for path in sorted(glob.glob(os.path.join(
            dump, "*after_optimizations.txt"))):
        name = os.path.basename(path).split(".")[1]
        with open(path) as f:
            found = scan(f.read(), shapes)
        slot = by_program.setdefault(name, {"modules": 0,
                                            "found": collections.Counter()})
        slot["modules"] += 1
        slot["found"].update(found)
    report = []
    for name, slot in sorted(by_program.items()):
        suspects = {f"{what}: {op}": n
                    for (what, op), n in slot["found"].items()
                    if op not in IN_PLACE and not op.endswith(":dus")}
        line = {"program": name, "modules": slot["modules"],
                "pool_shaped": {f"{what}: {op}": n for (what, op), n
                                in sorted(slot["found"].items())},
                "suspects": suspects}
        report.append(line)
        print(json.dumps({"program": name, "modules": slot["modules"],
                          "suspects": suspects}), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"pool_copies.{cfg['name']}.json"),
              "w") as f:
        json.dump({"shapes": shapes, "programs": report}, f, indent=1)
    print(json.dumps({"programs": len(report), "with_suspects": sum(
        1 for r in report if r["suspects"])}))


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""The spread behind a bound, as the driver reads it. JAX-free.

    python benchmark/spread.py results.jsonl

``results.jsonl`` holds one line per run: ``{"cell": ..., "set": 1|2,
"seed": n, "result": <run.py's last line>}``. For every cell and metric:
each set's median and spread (the distance between the quartiles over the
median), the wider of the two spreads, and how far the second set's
median lies from the first's. A bound is about five times the widest
spread over the cells, and never under 1%.
"""

from __future__ import annotations

import json
import sys


def quantile(xs: list, q: float) -> float:
    """Linear interpolation between the order statistics."""
    xs = sorted(xs)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(xs: list) -> float:
    med = quantile(xs, 0.5)
    return (quantile(xs, 0.75) - quantile(xs, 0.25)) / med if med else 0.0


def summarise(lines: list) -> dict:
    by: dict = {}
    for ln in lines:
        for name, m in ln["result"]["metrics"].items():
            by.setdefault(ln["cell"], {}).setdefault(name, {}).setdefault(
                ln.get("set", 1), []).append(m["value"])
    out: dict = {}
    for cell, ms in by.items():
        for name, sets in ms.items():
            meds = {s: quantile(v, 0.5) for s, v in sets.items()}
            row = {"n": {s: len(v) for s, v in sets.items()},
                   "median": meds,
                   "spread": {s: spread(v) for s, v in sets.items()}}
            row["widest_spread"] = max(row["spread"].values())
            if 1 in meds and 2 in meds and meds[1]:
                row["set2_vs_set1"] = meds[2] / meds[1] - 1.0
            out.setdefault(cell, {})[name] = row
    return out


def main() -> int:
    with open(sys.argv[1]) as f:
        lines = [json.loads(x) for x in f if x.strip()]
    bad = [ln for ln in lines if not ln["result"].get("correct")
           or ln["result"].get("failed")]
    summary = summarise(lines)
    for cell, ms in sorted(summary.items()):
        for name, row in sorted(ms.items()):
            print(f"{cell:40s} {name:14s} "
                  + " ".join(f"set{s}: {row['median'][s]:.4g} "
                             f"(spread {100 * row['spread'][s]:.2f}%, "
                             f"n={row['n'][s]})" for s in sorted(row["n"]))
                  + (f"  set2/set1 {100 * row['set2_vs_set1']:+.2f}%"
                     if "set2_vs_set1" in row else ""))
    widest: dict = {}
    for ms in summary.values():
        for name, row in ms.items():
            widest[name] = max(widest.get(name, 0.0), row["widest_spread"])
    for name, w in sorted(widest.items()):
        print(f"widest spread of {name}: {100 * w:.2f}%  ->  bound about "
              f"{max(0.01, 5 * w):.3f}")
    print(f"{len(lines)} runs, {len(bad)} not correct or with failures")
    return 0


if __name__ == "__main__":
    sys.exit(main())

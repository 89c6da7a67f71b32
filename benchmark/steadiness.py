#!/usr/bin/env python3
"""How steadily a cell repeats, cheaply: many windows on one boot. Never
imports JAX.

    python benchmark/steadiness.py --workload <cell> [--windows 8]
        [--seconds S] [--seed 1] [--variants '[{}, {"seed_jitter": {}}]']

One server is booted as run.py boots it; then the cell's traffic is
played ``--windows`` times, each a ramp, a window of ``--seconds`` and a
drain, each on another seed (``--seed`` + i), as a run would play it.
Window i uses the cell's traffic with ``variants[i % len(variants)]``
laid over it, so that two settings of a mix can be compared on one boot
under the same conditions. Every window's end-to-end numbers go on a
line of their own, every window's records into ``chiprun_out/`` (other
statistics can then be tried on them without the chip), and the last
lines give each variant's spread as the driver reads one.

This is a tool for the PR that sets or revisits a bound: a run costs
100 s of set-up for its window, this costs it once. It sees what varies
between windows, not what varies between boots; the bound is still set
from whole runs (spread.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import loadgen, manifest, metrics, run, spread  # noqa: E402


def main(argv=None, data_root: str = ROOT,
         out_root: str = os.path.join(ROOT, "chiprun_out")) -> int:
    """``data_root`` and ``out_root`` as in run.run_cell (a test hands
    in temporary ones)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--windows", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--variants", default="[{}]",
                    help="JSON list of objects laid over the traffic mix")
    args = ap.parse_args(argv)
    variants = json.loads(args.variants)
    cell = manifest.load_cell(args.workload, data_root)
    seconds = args.seconds or float(cell.run_seconds)
    out_dir = os.path.join(out_root, "benchmark",
                           f"{cell.name}.steadiness")
    os.makedirs(out_dir, exist_ok=True)
    port, ctl_port = run.free_port(), run.free_port()
    url, ctl = f"http://127.0.0.1:{port}", f"http://127.0.0.1:{ctl_port}"
    proc, log_path = run.start_child(cell, port, ctl_port, out_dir, False)
    rows: list = []
    try:
        run.wait_ready(url, proc, log_path)
        _, labels = run.scrape(url)
        run.check_device(run.get_json(ctl + "/device"), labels, cell)
        for i in range(args.windows):
            v = i % len(variants)
            tr = {**cell.traffic, **variants[v]}
            r = loadgen.Run("127.0.0.1", port, tr, args.seed + i,
                            run.RAMP_S, seconds)
            records = r.play()
            obs = metrics.Observations(records, run.RAMP_S, seconds)
            attempted, failed = metrics.counts(obs)
            row = {"window": i, "variant": v, "seed": args.seed + i,
                   "attempted": attempted, "failed": failed,
                   "drained": r.drained, **metrics.end_to_end(obs)}
            rows.append(row)
            print(json.dumps(row), flush=True)
            with open(os.path.join(out_dir, f"window{i}.json"), "w") as f:
                json.dump({"row": row, "traffic": tr, "ramp_s": run.RAMP_S,
                           "window_s": seconds,
                           "records": [vars(x) for x in records]}, f)
            run.wait_drained(url, 60.0)
            time.sleep(1.0)
    finally:
        run.stop_child(proc)
    for v, over in enumerate(variants):
        mine = [r for r in rows if r["variant"] == v]
        for name in ("ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms",
                     "itl_p50_ms", "out_tok_s"):
            xs = [r[name] for r in mine if r[name] is not None]
            if len(xs) >= 2:
                print(json.dumps({
                    "variant": over, "metric": name, "n": len(xs),
                    "median": spread.quantile(xs, 0.5),
                    "spread_pct": 100 * spread.spread(xs)}), flush=True)
    return 0 if rows and not any(r["failed"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())

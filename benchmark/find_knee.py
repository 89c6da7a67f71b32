#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, on the chip. Never imports JAX.

    python benchmark/find_knee.py --workload <cell> [--start 2] [--seconds 30]

One server is booted as run.py boots it; then the cell's traffic is
played at rates rising by a factor 1.15, each for ``--seconds`` after a
5 s ramp, with a drain in between. The knee is the highest rate at which
at least 90% of the requests due in the window met both limits
(metrics.SLO_TTFT_MS from the due time, metrics.SLO_TPOT_MS) and no more
requests were out at the window's end than at its middle. The sweep
stops at the second rate in a row that fails. The cell's fixed rate is
0.8 x the knee, rounded down to 0.5 requests/s, and is written by hand
into ``cells/<cell>.json`` with this sweep's table: a run never searches.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import loadgen, manifest, metrics, run  # noqa: E402


def out_at(records: list, t: float) -> int:
    """Requests sent and not yet ended at time ``t``."""
    return sum(1 for r in records if r.send_t is not None and r.send_t <= t
               and (r.end_t is None or r.end_t > t))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start", type=float, default=2.0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--max-rates", type=int, default=14)
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload, ROOT)
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark",
                           f"{cell.name}.knee")
    os.makedirs(out_dir, exist_ok=True)
    port, ctl_port = run.free_port(), run.free_port()
    url, ctl = f"http://127.0.0.1:{port}", f"http://127.0.0.1:{ctl_port}"
    proc, log_path = run.start_child(cell, port, ctl_port, out_dir, False)
    slo_share = manifest.load_reader(cell.root, "slo_share")
    table, knee, fails = [], None, 0
    try:
        run.wait_ready(url, proc, log_path)
        _, labels = run.scrape(url)
        run.check_device(run.get_json(ctl + "/device"), labels, cell)
        rate = args.start
        for i in range(args.max_rates):
            tr = dict(cell.traffic, rate_rps=rate)
            r = loadgen.Run("127.0.0.1", port, tr, args.seed + i,
                            run.RAMP_S, args.seconds, drain_s=60.0)
            records = r.play()
            obs = metrics.Observations(records, run.RAMP_S, args.seconds)
            share = slo_share(obs)
            mid = out_at(records, run.RAMP_S + args.seconds / 2)
            end = out_at(records, run.RAMP_S + args.seconds)
            e2e = metrics.end_to_end(obs)
            attempted, failed = metrics.counts(obs)
            # "No deeper": within the counting noise of a Poisson queue.
            good = (share is not None and share >= 90.0 and failed == 0
                    and end <= mid + max(2, math.sqrt(max(mid, 1))))
            row = {"rate_rps": round(rate, 3), "slo_share": share,
                   "out_mid": mid, "out_end": end, "attempted": attempted,
                   "failed": failed, "good": good, **e2e}
            table.append(row)
            print(json.dumps(row), flush=True)
            if good:
                knee, fails = rate, 0
            else:
                fails += 1
                if fails >= 2:
                    break
            run.wait_drained(url, 60.0)
            rate *= 1.15
            time.sleep(1.0)
    finally:
        run.stop_child(proc)
    fixed = math.floor(0.8 * knee * 2) / 2 if knee else None
    result = {"cell": cell.name, "knee_rps": knee, "fixed_rate_rps": fixed,
              "window_s": args.seconds, "table": table}
    with open(os.path.join(out_dir, "knee.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items() if k != "table"}),
          flush=True)
    return 0 if knee else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""One run of one cell of the benchmark. The parent: it never imports JAX.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It reads the cell (BENCHMARK.json, its configuration file, its traffic
file), starts benchmark/serve_cell.py as the one child that holds the
chip (forced onto the TPU: with no chip the child fails at boot and the
run fails, it never reports a CPU number), waits for /readyz, checks the
device and the answers, plays the traffic from this process (a 5 s ramp
that is not counted, then the window), and prints the contract's JSON
object as the last line of standard output. Everything else it learns
goes on earlier lines and into ``chiprun_out/``.

``--trace 0`` reports the cell's end-to-end metrics, with tracing off.
``--trace 1`` reports its per-layer metrics: the child records a
profiler trace of a stretch in the middle of the window (4 s on one
chip, 1 s on four) and reduces it itself, requests carry a trace header
and their spans are fetched from /admin/trace afterwards, and /metrics
is sampled at 2 Hz.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import loadgen, manifest, metrics, roofline, traffic  # noqa: E402

RAMP_S = 5.0
TRACE_STRETCH_S = 4.0
SAMPLE_HZ = 2.0
BOOT_TIMEOUT_S = 1100.0
# Inherited serving configuration would make the run something other
# than the cell (chip_smoke.py's list, plus the tracing variables).
_SCRUB = ("SERVE_", "PAGED_", "MODEL_CONFIG", "CKPT_DIR", "FAIL_POINTS",
          "LLM_MODEL", "QMM_", "JAX_PLATFORMS", "TRACE_", "LOG_LEVEL")
# Short on purpose: with the template head a probe stays under 128
# tokens, the smallest grain at which the prefix store would promote a
# prompt sent twice (and compile a dozen programs for it).
_PROBES = (("Lunch on Friday?", 24), ("Is the build green yet?", 40))
# The reference check's sample of tokens, the same in every run. It
# followed --seed until one seed in fifteen failed a correct routed
# model: the sample is two sequences, and where system and reference
# route one early token to different experts (a tie within bf16's
# rounding) every later position of that sequence is off, half the
# sample, and the median with it (4.9% on seed 42 against 1.3-2.9% on
# fourteen others). With the program's weights fixed too, the check now
# gives the same answer in every run of one program: on the chip 1.3%
# (mixtral-8x7b-v0.1-l6, this sample), 3.1% (mistral-7b-v0.3, every
# sample tried). PERF.md, PR 22.
REF_SAMPLE_SEED = 53


class RunFailure(Exception):
    pass


def say(obj: dict) -> None:
    """An earlier line of standard output."""
    print(json.dumps(obj), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(url: str, timeout: float = 30.0) -> tuple:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def get_json(url: str, timeout: float = 30.0) -> dict:
    status, raw = http_get(url, timeout)
    if status != 200:
        raise RunFailure(f"GET {url} answered {status}: {raw[:300]!r}")
    return json.loads(raw)


def scrape(url: str) -> tuple:
    """(/metrics series without labels -> value, serve_device_info
    labels). Parsing copied from chip_smoke.py."""
    status, raw = http_get(url + "/metrics")
    if status != 200:
        raise RunFailure(f"GET /metrics answered {status}")
    values, device = {}, {}
    for line in raw.decode().splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, _, val = line.rpartition(" ")
        if name.startswith("serve_device_info{"):
            device = dict(re.findall(r'(\w+)="([^"]*)"', name))
        elif "{" not in name:
            try:
                values[name] = float(val)
            except ValueError:
                pass
    return values, device


def log_tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"(no server log: {e})"


def child_env(cell, port: int, traced: bool) -> dict:
    env = {k: v for k, v in os.environ.items() if not k.startswith(_SCRUB)}
    env.update(cell.config.get("stack", {}))
    env.update(cell.extra.get("stack", {}))
    env.update(
        SERVE_BACKEND="tpu", MODEL_CONFIG=cell.config_name,
        SERVE_ADDR=f"127.0.0.1:{port}", JAX_PLATFORMS="tpu",
        TPU_LOG_DIR="disabled",     # libtpu's default is /tmp/tpu_logs
        SERVE_WARMUP=",".join(str(b) for b in
                              cell.traffic["warmup_buckets"]),
        # End-to-end numbers are taken with request tracing off; the
        # traced run samples every request and must hold all of them.
        TRACE_SAMPLE="1" if traced else "0",
        TRACE_STORE="65536" if traced else "16")
    if cell.chips > 1:
        env["SERVE_TP"] = str(cell.chips)
    return env


def start_child(cell, port: int, ctl_port: int, out_dir: str, traced: bool
                ) -> tuple:
    log_path = os.path.join(out_dir, "server.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "serve_cell.py"),
             "--config-file", cell.config_file, "--control-port",
             str(ctl_port), "--out-dir", out_dir, "--root", cell.root],
            cwd=ROOT, env=child_env(cell, port, traced), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)
    return proc, log_path


def stop_child(proc: subprocess.Popen) -> None:
    """End the server and anything it started (its own process group),
    and wait until it has ended."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def wait_ready(url: str, proc: subprocess.Popen, log_path: str) -> tuple:
    """(seconds until the HTTP front answered: weights and pool loaded;
    seconds from there until /readyz was 200: the warm-up)."""
    t0 = time.monotonic()
    t_live = None
    while True:
        code = proc.poll()
        if code is not None:
            raise RunFailure(
                f"the server exited with code {code} after "
                f"{time.monotonic() - t0:.0f} s, before it was ready; its "
                f"last log lines:\n{log_tail(log_path)}")
        try:
            status, raw = http_get(url + "/readyz", timeout=5)
        except OSError:
            status, raw = 0, b""
        if status and t_live is None:
            t_live = time.monotonic()
        if status == 200:
            return t_live - t0, time.monotonic() - t_live
        if status == 500:
            raise RunFailure(f"/readyz reports a terminal failure: "
                             f"{raw[:400]!r}\n{log_tail(log_path)}")
        if time.monotonic() - t0 > BOOT_TIMEOUT_S:
            raise RunFailure(f"/readyz not 200 after {BOOT_TIMEOUT_S:.0f} s; "
                             f"last log lines:\n{log_tail(log_path)}")
        time.sleep(0.5)


def probe(host: str, port: int, head: str, tail: str) -> list:
    """Two prompts, each sent alone and twice: the streams must be equal
    byte for byte, one character a token, the counts must be the
    server's."""
    faults = []
    for text, n in _PROBES:
        prompt = head + text + tail
        a = loadgen.send_alone(host, port, prompt, n, {"temperature": 0})
        b = loadgen.send_alone(host, port, prompt, n, {"temperature": 0})
        for r in (a, b):
            if not r.ok:
                faults.append(f"probe failed: {r.status} {r.error}")
            elif r.final.get("prompt_eval_count") != len(prompt) + 1:
                faults.append(
                    f"prompt_eval_count {r.final.get('prompt_eval_count')} "
                    f"for {len(prompt)} bytes + BOS")
            elif r.final.get("eval_count") != n or r.tokens != n:
                faults.append(f"asked {n} tokens, the server counted "
                              f"{r.final.get('eval_count')}, the client "
                              f"read {r.tokens} characters")
            elif "�" in r.text:
                faults.append("a streamed character is U+FFFD")
        if a.ok and b.ok and a.text != b.text:
            faults.append("the same prompt sent twice gave two streams")
    return faults


def check_device(device: dict, labels: dict, cell) -> dict:
    """The server must compute on a TPU of a kind the peaks table has,
    with the chips the cell asks for. Returns the kind's peaks."""
    if device["platform"] != "tpu" or labels.get("platform") != "tpu":
        raise RunFailure(f"the server computes on {device}, not a TPU")
    if device["count"] < cell.chips:
        raise RunFailure(f"{cell.name} needs {cell.chips} chips, JAX "
                         f"found {device['count']}")
    try:
        return roofline.peaks_for(device["kind"])
    except KeyError as e:
        raise RunFailure(str(e)) from None


class Monitor(threading.Thread):
    """The run's clockwork beside the load generator: /metrics at the
    window's two ends; in a traced run also at 2 Hz, and the trace of a
    stretch in the middle of the window."""

    def __init__(self, url: str, ctl: str, run: loadgen.Run, traced: bool,
                 chips: int = 1) -> None:
        super().__init__(daemon=True, name="bench-monitor")
        self.url, self.ctl, self.run_, self.traced = url, ctl, run, traced
        # The trace holds every chip's events, and what stop_trace takes
        # to write grows with them (11-32 s for 4 s of one chip; over
        # the 120 s allowed for 4 s of four: PERF.md, PR 25). The stretch
        # is TRACE_STRETCH_S chip-seconds.
        self.trace_stretch_s = TRACE_STRETCH_S / chips
        self.start_c, self.end_c = {}, {}
        self.samples: list = []
        self.stretch = ({}, {})
        self.stretch_s = 0.0
        self.trace_info: dict = {}
        self.window_end: dict = {}
        self.error: str = ""

    def _sleep_until(self, t: float) -> None:
        delay = self.run_.t0 + t - time.monotonic()
        if delay > 0:
            time.sleep(delay)

    def run(self) -> None:
        try:
            while not self.run_.t0:
                time.sleep(0.01)
            r = self.run_
            self._sleep_until(r.ramp_s)
            get_json(self.ctl + "/window_start")
            self.start_c = scrape(self.url)[0]
            if self.traced:
                mid = r.ramp_s + r.window_s / 2
                t_a = mid - self.trace_stretch_s / 2
                t_b = mid + self.trace_stretch_s / 2
                t, a, b = r.ramp_s, None, None
                while t < r.stop_t - 1.0 / SAMPLE_HZ:
                    t += 1.0 / SAMPLE_HZ
                    if a is None and t >= t_a:
                        # Counters are read inside the traced stretch, so
                        # that starting and stopping the profiler is not
                        # counted as time the steps took.
                        self._sleep_until(t_a)
                        get_json(self.ctl + "/trace_start", timeout=60)
                        a = (time.monotonic(), scrape(self.url)[0])
                    if a is not None and b is None and t >= t_b:
                        self._sleep_until(t_b)
                        b = (time.monotonic(), scrape(self.url)[0])
                        self.trace_info = get_json(self.ctl + "/trace_stop",
                                                   timeout=120)
                        self.stretch = (a[1], b[1])
                        self.stretch_s = b[0] - a[0]
                    self._sleep_until(t)
                    self.samples.append((time.monotonic() - r.t0,
                                         scrape(self.url)[0]))
            self._sleep_until(r.stop_t)
            self.end_c = scrape(self.url)[0]
            self.window_end = get_json(self.ctl + "/window_end")
        except Exception as e:  # noqa: BLE001 — the parent reports it
            self.error = f"{type(e).__name__}: {e}"


def fetch_spans(url: str, records: list) -> dict:
    """span name -> [dur_ms, ...] over the requests due in the window."""
    out: dict = {}
    for r in records:
        if not r.trace_id:
            continue
        status, raw = http_get(f"{url}/admin/trace?id={r.trace_id}")
        if status != 200:
            continue
        for s in json.loads(raw).get("spans", []):
            out.setdefault(s["name"], []).append(s["dur_ms"])
    return out


def wait_drained(url: str, timeout_s: float = 20.0) -> tuple:
    deadline = time.monotonic() + timeout_s
    while True:
        m = scrape(url)[0]
        free, total = (m.get("serve_kv_free_pages", -1),
                       m.get("serve_kv_total_pages", 0))
        if (free == total and not m.get("serve_batch_occupancy")) \
                or time.monotonic() > deadline:
            return free, total
        time.sleep(0.25)


def run_cell(args, t_start: float, data_root: str = ROOT,
             out_root: str = os.path.join(ROOT, "chiprun_out")) -> dict:
    """``data_root`` holds BENCHMARK.json and the files it names (a test
    hands in a temporary one); the program and the child are ROOT's."""
    cell = manifest.load_cell(args.workload, data_root)
    if not os.path.isdir(os.path.join(ROOT, "p2p_llm_chat_tpu")):
        raise RunFailure("no p2p_llm_chat_tpu package beside the benchmark: "
                         "there is no program here to measure")
    traced = bool(args.trace)
    out_dir = os.path.join(out_root, "benchmark",
                           f"{cell.name}.seed{args.seed}.trace{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    if cell.traffic["loop"] == "open" and not cell.traffic.get("rate_rps"):
        raise RunFailure(f"open-loop cell {cell.name} has no rate_rps "
                         f"(cells/{cell.name}.json)")
    port, ctl_port = free_port(), free_port()
    url, ctl = f"http://127.0.0.1:{port}", f"http://127.0.0.1:{ctl_port}"
    say({"cell": cell.name, "seed": args.seed, "seconds": args.seconds,
         "trace": args.trace, "rate_rps": cell.traffic.get("rate_rps"),
         "clients": cell.traffic.get("clients"),
         "lengths": traffic.describe(cell.traffic, args.seed, 500)})
    proc, log_path = start_child(cell, port, ctl_port, out_dir, traced)
    faults: list = []
    try:
        load_s, warm_s = wait_ready(url, proc, log_path)
        device = get_json(ctl + "/device")
        _, labels = scrape(url)
        peaks = check_device(device, labels, cell)
        t_checks = time.monotonic()
        p = cell.traffic["prompt"]
        faults += probe("127.0.0.1", port, p.get("head", ""),
                        p.get("tail", ""))
        ref = get_json(f"{ctl}/refcheck?seed={REF_SAMPLE_SEED}", timeout=600)
        if not ref.get("ok"):
            faults.append(f"the system disagrees with the reference: {ref}")
        checks_s = time.monotonic() - t_checks
        setup_s = time.monotonic() - t_start
        say({"setup": {"load_s": load_s, "warmup_s": warm_s,
                       "checks_s": checks_s, "setup_s": setup_s},
             "reference": ref})

        run = loadgen.Run("127.0.0.1", port, cell.traffic, args.seed,
                          RAMP_S, float(args.seconds), traced=traced)
        mon = Monitor(url, ctl, run, traced, cell.chips)
        mon.start()
        records = run.play()
        mon.join(timeout=180)
        if mon.is_alive() or mon.error:
            raise RunFailure(f"the monitor failed: {mon.error or 'hung'}")
        if not run.drained:
            faults.append("requests were still out when the drain ended")
        free, total = wait_drained(url)
        if free != total:
            faults.append(f"the KV pool did not drain: {free:.0f} of "
                          f"{total:.0f} pages free")
        n_comp = mon.window_end.get("compiles_in_window", -1)
        if n_comp:
            faults.append(f"{n_comp} compilations inside the window: "
                          f"{mon.window_end.get('compiled')}")
        obs = metrics.Observations(
            records=records, ramp_s=RAMP_S, window_s=float(args.seconds),
            cell=cell, counters_start=mon.start_c, counters_end=mon.end_c,
            samples=mon.samples, stretch_start=mon.stretch[0],
            stretch_end=mon.stretch[1], stretch_s=mon.stretch_s,
            device=mon.window_end.get("device", device), peaks=peaks)
        device = obs.device
        breakdown = None
        if traced:
            obs.spans = fetch_spans(url, obs.counted())
            red = get_json(f"{ctl}/trace_reduce"
                           f"{'?sample=1' if args.sample else ''}",
                           timeout=600)
            if not red.get("busy_s"):
                raise RunFailure(f"the device trace shows no operation on "
                                 f"the device: {red}")
            obs.trace = red
            device = {**device, "busy_s": red["busy_s"],
                      "window_s": red["window_s"]}
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            say({"trace": {k: v for k, v in red.items()
                           if k not in ("device_ops", "idle_gaps")},
                 "trace_stop": mon.trace_info,
                 "spans": {k: len(v) for k, v in obs.spans.items()}})
        if proc.poll() is not None:
            raise RunFailure("the server ended during the run")
    finally:
        stop_child(proc)

    attempted, failed = metrics.counts(obs)
    e2e = metrics.end_to_end(obs)
    e2e["setup_s"] = setup_s
    lag = [(r.send_t - r.due_t) * 1e3 for r in obs.counted()
           if r.send_t is not None]
    say({"window": {"attempted": attempted, "failed": failed,
                    "started_in_run": len(records),
                    "tokens_in_window": obs.tokens_in_window(),
                    "decode_steps": obs.decode_steps(),
                    "gen_lag_p99_ms": metrics.percentile(lag, 99),
                    "end_to_end_all": e2e, "faults": faults,
                    "errors": sorted({r.error[:120] for r in obs.counted()
                                      if not r.ok})[:5]}})
    reported: dict = {}
    if traced:
        for m in cell.per_layer:
            value = manifest.load_reader(cell.root, m["name"])(obs)
            if value is not None:
                reported[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = e2e.get(m["name"])
            if value is None:
                faults.append(f"no value for {m['name']}")
            else:
                reported[m["name"]] = {"value": value, "unit": m["unit"]}
    with open(os.path.join(out_dir, "records.json"), "w") as f:
        json.dump([vars(r) for r in records], f)
    last = {"correct": not faults, "attempted": attempted, "failed": failed,
            "metrics": reported,
            "device": {k: device[k] for k in
                       ("platform", "kind", "count", "memory_peak_bytes",
                        "busy_s", "window_s") if k in device}}
    if breakdown is not None:
        last["breakdown"] = breakdown
    if faults:
        say({"faults": faults})
    return last


def main() -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sample", action="store_true",
                    help="with --trace 1: also write the trace's first "
                         "events, to be looked at by hand")
    args = ap.parse_args()
    try:
        if args.seconds is None:
            args.seconds = manifest.load_manifest(ROOT)["run_seconds"]
        last = run_cell(args, t_start)
    except (RunFailure, manifest.ManifestError, KeyError, OSError) as e:
        print(f"benchmark: FAILED, no result: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

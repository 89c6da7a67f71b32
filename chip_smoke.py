#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Boots the serve plane the way an operator does — ``python -m
p2p_llm_chat_tpu.serve.api`` with ``SERVE_BACKEND=tpu`` — on
``MODEL_CONFIG=llama3.1-8b`` at its published widths and full depth
(random weights from a seed, byte tokenizer) in the repo's default
serving stack: int8 weights, paged int8 KV, prefix cache, chunked
prefill, fused decode. Then drives it over HTTP like a client:

- a co-pilot-template prompt (the UI's request; prefix-cache admit);
- one ~2.5k-token prompt (chunked prefill, then decode at a window
  >= 2048 — the flash-append kernel);
- 8 concurrent streamed generates;
- one /api/chat and one /api/embed;
- one seeded request posted twice (byte-identical).

and checks the answers, the /metrics counters those paths must move, the
KV pool draining back to its total, and that the device the SERVER
reports is a TPU. Any failed step — a child that dies, a /readyz that
never turns 200, a wrong answer — exits non-zero.

This process never imports JAX: a chip belongs to one process, and that
process is the server. The child is forced onto the TPU
(``JAX_PLATFORMS=tpu``, whatever this environment exports), so with no
chip it fails at boot instead of serving from the CPU.

Sizes: SERVE_SLOTS=8, SERVE_MAX_SEQ=4096 (decode windows 128..4096),
SERVE_WARMUP=128,4096 (the two prompt buckets the requests land in).
int8 weights ~8.6 GB + int8 KV pool ~2.2 GB of the chip's 16 GB.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # SERVE_TP=4 on a four-chip host

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import importlib.metadata
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out")

CONFIG = "llama3.1-8b"
SERVER_ENV = {
    "SERVE_BACKEND": "tpu",
    "MODEL_CONFIG": CONFIG,
    "SERVE_QUANT": "int8",
    "SERVE_KV": "paged",
    "SERVE_KV_QUANT": "int8",
    "SERVE_SLOTS": "8",
    "SERVE_MAX_SEQ": "4096",
    "SERVE_WARMUP": "128,4096",
}
# Everything must finish inside the contract's 1200 s; the boot (weight
# init + every warmup compile from a cold cache) is nearly all of it.
BOOT_TIMEOUT_S = 960.0
REQUEST_TIMEOUT_S = 120.0
LONG_PROMPT_TOKENS = 2500
# Inherited serving configuration would make the run something other
# than the configuration named above.
_SCRUB = ("SERVE_", "PAGED_", "MODEL_CONFIG", "CKPT_DIR", "FAIL_POINTS",
          "LLM_MODEL", "QMM_", "JAX_PLATFORMS")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body: dict | None = None,
         timeout: float = REQUEST_TIMEOUT_S) -> tuple[int, bytes]:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def post(url: str, path: str, body: dict) -> dict:
    status, raw = http("POST", url + path, body)
    if status != 200:
        raise SmokeFailure(f"POST {path} answered {status}: {raw[:300]!r}")
    return json.loads(raw)


def post_stream(url: str, path: str, body: dict) -> dict:
    """POST with ``stream: true``; returns the final NDJSON record."""
    status, raw = http("POST", url + path, {**body, "stream": True})
    if status != 200:
        raise SmokeFailure(f"POST {path} (stream) answered {status}: "
                           f"{raw[:300]!r}")
    lines = [json.loads(line) for line in raw.splitlines() if line.strip()]
    if not lines:
        raise SmokeFailure(f"POST {path} (stream) sent no NDJSON lines")
    if any("error" in rec for rec in lines):
        raise SmokeFailure(f"POST {path} (stream) carried an error record: "
                           f"{lines[-1]}")
    return lines[-1]


def metrics(url: str) -> tuple[dict[str, float], dict[str, str]]:
    """(/metrics unlabeled series -> value, serve_device_info labels)."""
    status, raw = http("GET", url + "/metrics", timeout=30)
    if status != 200:
        raise SmokeFailure(f"GET /metrics answered {status}")
    values: dict[str, float] = {}
    device: dict[str, str] = {}
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


def log_tail(path: str, n: int = 25) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"(no server log: {e})"


def wait_ready(url: str, proc: subprocess.Popen, log_path: str
               ) -> tuple[float, float]:
    """Poll until /readyz is 200. Returns (seconds until the HTTP front
    answered — weights and KV pool loaded — and seconds from there until
    ready — the warmup compiles)."""
    t0 = time.monotonic()
    t_live = None
    while True:
        code = proc.poll()
        if code is not None:
            raise SmokeFailure(
                f"server exited with code {code} after "
                f"{time.monotonic() - t0:.0f} s, before it was ready; its "
                f"last log lines:\n{log_tail(log_path)}")
        try:
            status, raw = http("GET", url + "/readyz", timeout=5)
        except OSError:
            status, raw = 0, b""
        if status and t_live is None:
            t_live = time.monotonic()
        if status == 200:
            return t_live - t0, time.monotonic() - t_live
        if status == 500:
            raise SmokeFailure(f"/readyz reports a terminal failure: "
                               f"{raw[:400]!r}\n{log_tail(log_path)}")
        if time.monotonic() - t0 > BOOT_TIMEOUT_S:
            raise SmokeFailure(
                f"/readyz not 200 after {BOOT_TIMEOUT_S:.0f} s (last status "
                f"{status}); last log lines:\n{log_tail(log_path)}")
        time.sleep(1.0)


def done_with_tokens(rec: dict, what: str, min_tokens: int = 1) -> None:
    check(rec.get("done") is True and rec.get("eval_count", 0) >= min_tokens,
          f"{what}: done with {rec.get('eval_count')} tokens "
          f"(prompt {rec.get('prompt_eval_count')})")


def drive(url: str, tp: int = 1) -> dict[str, str]:
    """Send the requests, check the answers and the counters. Returns
    the device labels the server reports."""
    from p2p_llm_chat_tpu.ui import SUGGEST_TEMPLATE   # JAX-free import

    _, device = metrics(url)
    check(device.get("platform") == "tpu" and bool(device.get("device_kind"))
          and int(device.get("count", 0)) >= tp,
          f"server reports device {device}")

    print("co-pilot template prompt", flush=True)
    rec = post(url, "/api/generate", {
        "prompt": SUGGEST_TEMPLATE.format(msg="Are we still on for lunch?"),
        "stream": False, "options": {"num_predict": 16}})
    done_with_tokens(rec, "co-pilot suggestion")

    print(f"long prompt (~{LONG_PROMPT_TOKENS} tokens)", flush=True)
    sentence = "The quick brown fox jumps over the lazy dog near the river. "
    long_prompt = (sentence * (LONG_PROMPT_TOKENS // len(sentence) + 1)
                   )[:LONG_PROMPT_TOKENS]
    rec = post(url, "/api/generate", {
        "prompt": long_prompt, "stream": False,
        "options": {"num_predict": 40}})
    done_with_tokens(rec, "long prompt", min_tokens=32)
    long_ctx = rec["prompt_eval_count"] + rec["eval_count"]

    print("8 concurrent streamed generates", flush=True)
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        futs = [pool.submit(post_stream, url, "/api/generate", {
            "prompt": f"Peer {i} asks: what is {i} plus {i}?",
            "options": {"num_predict": 16}}) for i in range(8)]
        for i, fut in enumerate(futs):
            done_with_tokens(fut.result(timeout=REQUEST_TIMEOUT_S + 30),
                             f"stream {i}")

    print("/api/chat and /api/embed", flush=True)
    rec = post(url, "/api/chat", {
        "messages": [{"role": "user", "content": "Hello there"}],
        "stream": False, "options": {"num_predict": 8}})
    done_with_tokens(rec, "chat")
    check(rec.get("message", {}).get("role") == "assistant",
          "chat answers as the assistant")
    rec = post(url, "/api/embed", {"input": ["hello world", "second text"]})
    vecs = rec.get("embeddings") or []
    check(len(vecs) == 2 and vecs[0] != vecs[1] and all(
        len(v) == 4096 and abs(math.fsum(x * x for x in v) - 1.0) < 1e-2
        for v in vecs),
        "embed: 2 distinct unit vectors of the model's width (4096)")

    print("seeded request, twice", flush=True)
    seeded = {"prompt": "Write one line about the sea.", "stream": False,
              "options": {"seed": 1234, "temperature": 0.8,
                          "num_predict": 24}}
    a = post(url, "/api/generate", seeded)
    b = post(url, "/api/generate", seeded)
    done_with_tokens(a, "seeded #1")
    check(a["response"] == b["response"] and a["context"] == b["context"],
          "seeded pair is byte-identical")

    print("/metrics", flush=True)
    for _ in range(20):      # the pool drains as the last rows release
        m, _ = metrics(url)
        free = m.get("serve_kv_free_pages", -1)
        total = m.get("serve_kv_total_pages", 0)
        if free == total:
            break
        time.sleep(0.5)
    check(total > 0 and free == total,
          f"KV pool drained: {free:.0f} of {total:.0f} pages free")
    for name in ("decode_fused_ticks_total", "prefill_chunks_total",
                 "serve_prefix_admits_total"):
        check(m.get(name, 0) > 0, f"{name} = {m.get(name)}")
    min_w = m.get("paged_flash_min_w", -1)
    if tp == 1:
        check(long_ctx > min_w > 0,
              f"the long request decoded at a {long_ctx}-token context, "
              f"past the flash-append boundary {min_w:.0f}")
    else:
        # Under a mesh the pool is sharded over kv heads, pallas_call
        # cannot consume it, and XLA's gather path serves every window.
        check(min_w == 0 and long_ctx > 2048,
              f"paged_flash_min_w = 0 under SERVE_TP={tp} (XLA gather at "
              f"every window); long request context {long_ctx}")
    check(m.get("serve_errors_total", 0) == 0, "serve_errors_total = 0")
    return device


def stop(proc: subprocess.Popen) -> None:
    """End the server and anything it started (its own process group)."""
    if proc.poll() is None:
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="SERVE_TP for the server (default 1: one chip)")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "p2p_llm_chat_tpu")):
        print(f"chip_smoke: FAIL — no p2p_llm_chat_tpu package beside "
              f"{__file__}: there is no program here to start",
              file=sys.stderr)
        return 1
    os.makedirs(LOG_DIR, exist_ok=True)
    log_path = os.path.join(LOG_DIR, f"chip_smoke_server_tp{args.tp}.log")
    port = free_port()
    url = f"http://127.0.0.1:{port}"
    env = {k: v for k, v in os.environ.items() if not k.startswith(_SCRUB)}
    env.update(SERVER_ENV, JAX_PLATFORMS="tpu",
               SERVE_ADDR=f"127.0.0.1:{port}")
    if args.tp > 1:
        env["SERVE_TP"] = str(args.tp)

    def version(pkg: str) -> str:
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return "not installed"

    print(f"chip_smoke: {CONFIG} tp={args.tp} "
          f"{' '.join(f'{k}={v}' for k, v in SERVER_ENV.items())}")
    print(f"chip_smoke: jax {version('jax')}, jaxlib {version('jaxlib')}, "
          f"libtpu {version('libtpu')}; server log {log_path}", flush=True)

    t_start = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "p2p_llm_chat_tpu.serve.api"],
            cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True)
    try:
        load_s, warm_s = wait_ready(url, proc, log_path)
        print(f"chip_smoke: ready — load {load_s:.0f} s, warmup compile "
              f"{warm_s:.0f} s", flush=True)
        device = drive(url, args.tp)
        check(proc.poll() is None, "server still running after the requests")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL after {time.monotonic() - t_start:.0f} s — "
              f"{e}", file=sys.stderr, flush=True)
        return 1
    finally:
        stop(proc)
    print(f"chip_smoke: PASS in {time.monotonic() - t_start:.0f} s — "
          f"{CONFIG} served on {device['platform']} "
          f"{device['device_kind']!r} x{device['count']} (boot "
          f"{load_s + warm_s:.0f} s = load {load_s:.0f} + warmup compile "
          f"{warm_s:.0f})")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": int(device["count"])}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

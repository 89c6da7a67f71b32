#!/usr/bin/env python3
"""The one child of a run: the process that holds the chip.

It builds the cell's ``ModelConfig`` from the configuration file's
published fields (through the family's architecture file, which also
holds the plain reference and the reader of the engine's tree:
manifest.py), registers it under the configuration's name, installs
the benchmark tokenizer, and calls ``p2p_llm_chat_tpu.serve.api.main()``:
the normal entry point, scheduler, cache and HTTP front. The serving
stack's settings arrive as ``SERVE_*`` variables from the parent
(run.py). Beside the program's own HTTP front it listens on a control
port of its own, because only the process that holds the chip can do
these: compare the system with the plain reference on the engine's own
weights, count compilations, record and reduce a profiler trace, read
the device's memory statistics.

The two hooks into the program, both pinned by tests so that a refactor
which breaks them fails loudly: the random-weights path takes its
tokenizer from the module global ``serve.engine.ByteTokenizer``, and
``api.main`` looks ``build_engine_from_env`` up in ``serve.engine`` when
it is called.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

BENCH_ROOT = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_ROOT)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# One printable character for every id: the CJK Unified Ideographs block
# (U+4E00..U+9FFF, 20,992 assigned letters, none combining, none a
# control). Ids wrap; the client counts characters, it does not decode.
_GLYPH_BASE = 0x4E00
_GLYPH_SPAN = 0x9FFF - 0x4E00 + 1

# The reference check's sample: sequences x (prefill + decode) tokens.
REF_SEQS = 2
REF_PREFILL = 128
REF_DECODE = 8


def make_tokenizer_class():
    from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

    class BenchTokenizer(ByteTokenizer):
        """``ByteTokenizer`` with nothing hidden: ``decode`` renders every
        id as exactly one printable character (the program's drops every
        id >= 256, which is 99% of what random weights generate), and no
        id stops a stream, so a request generates exactly its
        ``num_predict`` tokens. ``encode`` is the program's: a prompt
        byte is a token."""

        def __init__(self, vocab_size: int = 512) -> None:
            super().__init__(vocab_size)
            self.eos_id = -1            # out of range: never a stop id

        def decode(self, ids) -> str:
            return "".join(chr(_GLYPH_BASE + i % _GLYPH_SPAN) for i in ids)

    return BenchTokenizer


def architecture(cfg: dict, root: str = BENCH_ROOT):
    """The configuration's architecture file, loaded: everything
    particular to its model family (manifest.py has the contract)."""
    from benchmark import manifest
    return manifest.load_architecture(
        root, cfg.get("architecture", manifest.DEFAULT_ARCHITECTURE))


def model_config(cfg: dict, root: str = BENCH_ROOT):
    """The program's ``ModelConfig``, from the configuration file by way
    of its family's architecture file."""
    import dataclasses
    from benchmark import manifest
    from p2p_llm_chat_tpu.models.configs import ModelConfig
    arch = architecture(cfg, root)
    kwargs = arch.model_config(cfg)
    unknown = sorted(set(kwargs)
                     - {f.name for f in dataclasses.fields(ModelConfig)})
    if unknown:
        raise manifest.ManifestError(
            f"{arch.__file__}: model_config() returns {unknown}, which "
            f"the program's ModelConfig does not have")
    return ModelConfig(**kwargs)


def install(cfg: dict, captured: dict, root: str = BENCH_ROOT) -> None:
    """Register the configuration, install the tokenizer, and wrap the
    engine builder so that the control port can reach the engine."""
    from p2p_llm_chat_tpu.models import configs
    from p2p_llm_chat_tpu.serve import engine
    configs.CONFIGS[cfg["name"]] = model_config(cfg, root)
    engine.ByteTokenizer = make_tokenizer_class()
    build = engine.build_engine_from_env

    def build_and_keep():
        backend = build()
        captured["backend"] = backend
        return backend

    engine.build_engine_from_env = build_and_keep


class CompileLog:
    """Every program JAX compiled, or fetched from its persistent cache,
    in this process, with the time: a shape new to the process shows as
    one or the other."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        self.times: list = []
        self._mu = threading.Lock()

    def listen(self) -> None:
        import jax.monitoring

        def on_event(name: str, secs: float, **kw) -> None:
            if name in self.EVENTS:
                with self._mu:
                    self.times.append((time.monotonic(), name, secs))
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def between(self, t0: float, t1: float) -> list:
        with self._mu:
            return [(n, s) for t, n, s in self.times if t0 <= t <= t1]


# -- the reference check ------------------------------------------------------

def system_logits(sched, tokens, n_prefill: int):
    """The system's logits for ``tokens`` [B, P+D]: prefill of the first
    P = ``n_prefill``, spliced into a paged int8 cache as admission
    does, then D decode steps through it, with the model functions, on
    the parameter tree and under the mesh the scheduler serves with
    (``None`` on one chip). Returns [B, P+D, V] float32."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.models.llama import KVCache
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_batch)
    model, params, config = sched._model, sched._params, sched.config
    mesh = sched.mesh
    B, T = tokens.shape
    P = n_prefill
    ps = sched.page_size
    per_row = -(-(T + 1) // ps)
    window_pages = 1
    while window_pages * ps < T + 1:
        window_pages *= 2
    per_row = max(per_row, window_pages)
    lens = jnp.full((B,), P, jnp.int32)
    rows = jnp.arange(B, dtype=jnp.int32)
    tables = 1 + jnp.arange(B * per_row, dtype=jnp.int32).reshape(B, per_row)

    @jax.jit
    def prefill(params, toks):
        small = KVCache.create(config, B, P, dtype=sched._dtype)
        logits, small = model.prefill(params, config, toks, lens, small,
                                      mesh, last_only=False)
        cache = PagedKVCache.create(config, B, 1 + B * per_row, ps,
                                    max_pages_per_row=per_row,
                                    quantized=sched.kv_quant, mesh=mesh)
        return logits, write_prefill_batch(cache, small.k, small.v, rows,
                                           lens, tables)

    @jax.jit
    def decode(params, tok, cache):
        return model.decode_step_paged(params, config, tok, cache, mesh,
                                       pages=window_pages)

    logits, cache = prefill(params, tokens[:, :P])
    out = [logits.astype(jnp.float32)]
    for t in range(P, T):
        step, cache = decode(params, tokens[:, t:t + 1], cache)
        out.append(step.astype(jnp.float32))
    return jnp.concatenate(out, axis=1)


def reference_check(sched, cfg: dict, seed: int,
                    root: str = BENCH_ROOT) -> dict:
    """Compare the system with its family's plain reference on REF_SEQS
    seeded sequences: REF_PREFILL tokens of prefill, then REF_DECODE
    decode steps. Everything particular to the family (how the engine's
    tree is read, the reference, the verdict) is its architecture
    file's."""
    import jax.numpy as jnp
    import numpy as np

    t0 = time.monotonic()
    arch = architecture(cfg, root)
    drive = getattr(arch, "system_logits", None)
    if drive is None:
        if sched.kv_mode != "paged":
            return {"ok": False, "error": "the check drives the paged "
                                          f"cache; SERVE_KV is "
                                          f"{sched.kv_mode!r}"}
        drive = system_logits
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(
        0, sched.config.vocab_size,
        size=(REF_SEQS, REF_PREFILL + REF_DECODE)), jnp.int32)
    system = drive(sched, tokens, REF_PREFILL)
    ref, facts = arch.forward(cfg, tokens, arch.engine_weights(sched))
    out = arch.compare(system, ref, {**facts, "n_prefill": REF_PREFILL},
                       cfg)
    out["seconds"] = time.monotonic() - t0
    return out


# -- the control port ---------------------------------------------------------

class Control:
    def __init__(self, cfg: dict, out_dir: str, captured: dict,
                 compiles: CompileLog, root: str = BENCH_ROOT) -> None:
        self.cfg, self.out_dir, self.root = cfg, out_dir, root
        self.captured, self.compiles = captured, compiles
        self.window_t0 = None
        self.trace_dir = os.path.join(out_dir, "trace")
        self.trace_t = None

    def _sched(self):
        backend = self.captured.get("backend")
        if backend is None:
            raise RuntimeError("the engine is not built yet")
        return backend.scheduler

    def device(self) -> dict:
        import jax
        devs = jax.local_devices()
        stats = [d.memory_stats() or {} for d in devs]
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(jax.devices()),
                "memory_peak_bytes": max(
                    int(s.get("peak_bytes_in_use", 0)) for s in stats),
                "bytes_in_use": [int(s.get("bytes_in_use", 0))
                                 for s in stats],
                "bytes_limit": [int(s.get("bytes_limit", 0))
                                for s in stats]}

    def handle(self, path: str, query: dict) -> dict:
        if path == "/device":
            return self.device()
        if path == "/refcheck":
            return reference_check(self._sched(), self.cfg,
                                   int(query.get("seed", 0)), self.root)
        if path == "/window_start":
            self.window_t0 = time.monotonic()
            return {"compiles_before": len(self.compiles.times)}
        if path == "/window_end":
            now = time.monotonic()
            inside = self.compiles.between(self.window_t0 or now, now)
            return {"compiles_in_window": len(inside),
                    "compiled": inside[:20], "device": self.device()}
        if path == "/trace_start":
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # no per-call Python events
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.trace_t = time.monotonic()
            return {}
        if path == "/trace_stop":
            import jax
            wall = time.monotonic() - self.trace_t
            jax.profiler.stop_trace()
            return {"wall_s": wall,
                    "stop_s": time.monotonic() - self.trace_t - wall}
        if path == "/trace_reduce":
            from benchmark import trace_reduce
            t0 = time.monotonic()
            out = trace_reduce.reduce(self.trace_dir)
            out["reduce_s"] = time.monotonic() - t0
            if query.get("sample"):
                with open(os.path.join(self.out_dir, "trace_sample.json"),
                          "w") as f:
                    json.dump(trace_reduce.sample(self.trace_dir), f)
            with open(os.path.join(self.out_dir, "trace_reduce.json"),
                      "w") as f:
                json.dump(out, f)
            # The trace itself is tens of megabytes; what comes back
            # from the machine with the chip is capped.
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            return out
        raise KeyError(path)


def serve_control(ctl: Control, port: int) -> None:
    import urllib.parse

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self) -> None:
            parsed = urllib.parse.urlsplit(self.path)
            query = {k: v[0] for k, v in
                     urllib.parse.parse_qs(parsed.query).items()}
            try:
                status, body = 200, ctl.handle(parsed.path, query)
            except KeyError:
                status, body = 404, {"error": f"no {parsed.path}"}
            except Exception as e:  # noqa: BLE001 — report, keep serving
                import traceback
                traceback.print_exc()
                status, body = 500, {"error": f"{type(e).__name__}: {e}"}
            raw = json.dumps(body).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(raw)))
            self.end_headers()
            self.wfile.write(raw)

        def log_message(self, fmt, *args) -> None:
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="bench-control").start()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--control-port", type=int, required=True)
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--root", default=BENCH_ROOT,
                    help="the directory of the cell's data files (the "
                         "first of BENCHMARK.json's paths): its "
                         "architectures/ holds the configuration's")
    args = ap.parse_args()
    with open(args.config_file) as f:
        cfg = json.load(f)
    os.makedirs(args.out_dir, exist_ok=True)
    captured: dict = {}
    compiles = CompileLog()
    compiles.listen()
    install(cfg, captured, args.root)
    serve_control(Control(cfg, args.out_dir, captured, compiles, args.root),
                  args.control_port)
    from p2p_llm_chat_tpu.serve import api
    api.main()


if __name__ == "__main__":
    main()

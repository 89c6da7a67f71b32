"""Device time by operation of ONE prefill chunk program and ONE decode
step of a benchmark configuration, on the chip, without a warm-up.

Boots what a benchmark cell's child boots (as tools/check_reference_limit.py
does: the configuration file, its stack, random weights, ``SERVE_WARMUP=0``,
no HTTP), calls the family's ``prefill_chunk[_counted]`` on a one-row carry
of ``SERVE_MAX_SEQ`` positions at each ``--offsets`` and its
``decode_step_paged`` at each ``--windows`` over ``--rows`` live rows of
``--context`` positions in the scheduler's own pool, and the
scheduler's own ``mid`` chunk program on a ladder of dummy entries at each
``--padded`` offset (what a chunk past every row's prompt costs: since
PR 50 a launch, before it the whole forward), and with ``--sampler`` the
scheduler's own decode program (the model's step and the sampler behind
it) over the same rows at each temperature given, each under
``jax.profiler``, and prints the forty operations that took most self
time (``benchmark/trace_reduce.reduce``). Two minutes a call for a
routed hybrid model; a cell's traced run keeps ten operations of a 4 s
stretch in which decode and prefill programs interleave, which names a
bottleneck and not its parts (PERF.md section 6, PR 49: each of the
Keye cell's four was found with this).

    python tools/profile_programs.py benchmark/configs/<name>.json \\
        --offsets 1024,8192,15360 --windows 16384,8192

Where the configuration's architecture file has ``kernel_costs`` (a
kernel's operations and bytes a layer), each of its kernels found among
the operations also gets its share of the roofline: the larger of
operations over the chip's bf16 peak and bytes over its HBM rate
(benchmark/peaks.json), times the layers, over the kernel's device time.

Writes ``chiprun_out/profile.<name>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_file")
    ap.add_argument("--offsets", default="1024,8192")
    ap.add_argument("--windows", default="16384")
    ap.add_argument("--padded", default="",
                    help="offsets of the top bucket's ladder at which to "
                    "run the scheduler's chunk program on padding alone")
    ap.add_argument("--sampler", default="",
                    help="temperatures of the live rows at which to run "
                    "the scheduler's own decode program, sampler and all, "
                    "at each of --windows (0: greedy rows; above 0 with "
                    "top-k 40 and top-p 0.9)")
    ap.add_argument("--fuse", type=int, default=1,
                    help="steps of the scheduler's decode program that "
                    "--sampler runs: 1 the plain step, more the fused scan")
    ap.add_argument("--carry", type=int, default=0,
                    help="positions of the chunks' dense carry (0: "
                    "SERVE_MAX_SEQ; a looped stack's carry of 2,048 is "
                    "3.2 GB beside its pool)")
    ap.add_argument("--rows", type=int, default=14)
    ap.add_argument("--context", type=int, default=9300)
    args = ap.parse_args()
    with open(args.config_file) as f:
        cfg = json.load(f)
    os.environ.update(cfg.get("stack", {}))
    os.environ.update(SERVE_BACKEND="tpu", MODEL_CONFIG=cfg["name"],
                      SERVE_WARMUP="0")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import roofline, serve_cell, trace_reduce
    from p2p_llm_chat_tpu.models.llama import KVCache
    from p2p_llm_chat_tpu.serve import engine

    serve_cell.install(cfg, {})
    backend = engine.build_engine_from_env()
    sched = backend.scheduler
    model, params, config = sched._model, sched._params, sched.config
    arch = serve_cell.architecture(cfg)
    peaks = roofline.peaks_for(jax.devices()[0].device_kind)
    out: dict = {}

    def shares(ops_ms: list, queries: float, pairs: float,
               keys: float) -> list:
        """[kernel, ms a call, % of its roofline] of every kernel the
        architecture file counts that is among ``ops_ms``."""
        found = []
        for kernel in getattr(arch, "KERNELS", ()):
            ms = sum(t for op, t in ops_ms if op.startswith(f"%{kernel}."))
            if not ms:
                continue
            flops, nbytes = arch.kernel_costs(cfg, kernel, queries, pairs,
                                              keys)
            least_s = cfg["num_hidden_layers"] * max(
                flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
            found.append([kernel, ms, 100.0 * least_s * 1e3 / ms])
        return found

    def traced(name: str, fn, n: int, queries: float, pairs: float,
               keys: float) -> None:
        jax.block_until_ready(fn())         # compile, and run once
        # A directory of this trace's own, under TMPDIR where the caller
        # set one: a fixed path would be shared by two checkouts on one
        # machine and could hand find_xplane another run's file.
        trace_dir = tempfile.mkdtemp(prefix=f"profile_{name}.")
        try:
            jax.profiler.start_trace(trace_dir)
            t0 = time.monotonic()
            for _ in range(n):
                result = fn()
            jax.block_until_ready(result)
            wall = (time.monotonic() - t0) / n
            jax.profiler.stop_trace()
            red = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir),
                                      top=40)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ops_ms = [[k, v / n * 1e3] for k, v in red["device_ops"]]
        out[name] = {"wall_ms": wall * 1e3,
                     "busy_ms": red["busy_s"] / n * 1e3,
                     "ops_ms": ops_ms,
                     "kernel_roofline": shares(ops_ms, queries, pairs, keys)}

    C = sched.prefill_chunk
    for off in map(int, filter(None, args.offsets.split(","))):
        carry = KVCache.create(config, 1, args.carry or sched.max_seq,
                               dtype=sched._dtype)
        toks = jnp.full((1, C), 7, jnp.int32)
        valid = jnp.ones((1, C), bool)
        # The dense family's chunk takes no mask of real positions and
        # hands no counts back.
        chunk = jax.jit(lambda p, t, v, c, off=off: (
            model.prefill_chunk_counted(
                p, config, t, c, off, v, None,
                last_idx=jnp.zeros((1,), jnp.int32))
            if hasattr(model, "prefill_chunk_counted")
            else model.prefill_chunk(
                p, config, t, c, off, None,
                last_idx=jnp.zeros((1,), jnp.int32)))[0])
        traced(f"chunk_at_{off}", lambda: chunk(params, toks, valid, carry),
               2, C, C * off + C * (C + 1) / 2, off + C)
    S = sched.max_seq
    for off in map(int, filter(None, args.padded.split(","))):
        if not 0 < off < S - C or off % C:
            raise SystemExit(f"--padded {off}: not a mid chunk of a ladder "
                             f"of {C} to {S}")
        # The loop is idle (no warm-up, no HTTP): its dispatch, called
        # from here, on the buffer a warm-up job would build. The carry
        # is donated, so each call takes the last one's.
        packed = sched._admit_upload(
            sched._admit_host_arrays([], [], S, 1, None), live=False)
        held = [KVCache.create(config, 1, S, dtype=sched._dtype),
                sched._chunk_logits0(1)]

        def padded(off=off, packed=packed, held=held):
            held[0], held[1], _ = sched._dispatch_prefill_chunk(
                0, S, off, C, packed, held[0], held[1], None)
            return sched._last_out
        traced(f"padded_chunk_at_{off}", padded, 4, 0, 0, 0)
    cache = sched._cache
    ps = sched.page_size
    per_row = -(-(args.context + 1) // ps)
    slots = cache.page_table.shape[0]
    table = np.zeros(cache.page_table.shape, np.int32)
    lens = np.zeros((slots,), np.int32)
    stride = max(1, slots // args.rows)
    for r in range(args.rows):
        table[r * stride, :per_row] = 1 + r * per_row + np.arange(per_row)
        lens[r * stride] = args.context
    if 1 + args.rows * per_row > sched.num_pages:
        raise SystemExit(f"{args.rows} rows of {args.context} positions "
                         f"need more than the pool's {sched.num_pages} pages")
    toks = jnp.full((slots, 1), 5, jnp.int32)
    for W in map(int, filter(None, args.windows.split(","))):
        held = cache._replace(
            page_table=jnp.asarray(table),
            lengths=jnp.asarray(np.minimum(lens, W - 1)))
        step = jax.jit(lambda p, t, c, a, W=W: model.decode_step_paged(
            p, config, t, c, None, active=a, pages=W // ps)[0])
        in_ctx = args.rows * (min(args.context, W - 1) + 1)   # own too
        traced(f"decode_window_{W}",
               lambda: step(params, toks, held, jnp.asarray(lens > 0)), 4,
               args.rows, in_ctx, in_ctx)
    # The scheduler's own decode program, sampler and all, over the same
    # rows: what it takes beyond the model's step above is the sampler's
    # (models/sampling.sample_step_batched). The program donates the
    # pool, so these run last and hand the pool on; a step advances a
    # live row's length, so the rows start 128 positions short.
    live = jnp.asarray(lens > 0)
    for W in map(int, filter(None, args.windows.split(","))):
        for temp in map(float, filter(None, args.sampler.split(","))):
            prog = (sched._decode_fused_for(W, args.fuse) if args.fuse > 1
                    else sched._decode_for(W))
            held = [jnp.full((slots, 1), 5, jnp.int32), cache._replace(
                        page_table=jnp.asarray(table),
                        lengths=jnp.asarray(np.minimum(lens, W - 128))),
                    jax.vmap(jax.random.PRNGKey)(jnp.arange(slots)),
                    sched._ring_dev]
            temps = jnp.full((slots,), temp, jnp.float32)
            top_ks = jnp.full((slots,), 40 if temp > 0 else 0, jnp.int32)
            top_ps = jnp.full((slots,), 0.9 if temp > 0 else 1.0,
                              jnp.float32)

            def sched_step(prog=prog, held=held, temps=temps, top_ks=top_ks,
                           top_ps=top_ps):
                out_toks, *held[:] = prog(
                    params, held[0], held[1], live, temps, top_ks, top_ps,
                    held[2], held[3], sched._rps_dev)
                return out_toks
            in_ctx = args.rows * (min(args.context, W - 128) + 1)
            traced(f"sched_decode{args.fuse}_window_{W}_temperature_{temp:g}",
                   sched_step, 4, args.rows, in_ctx, in_ctx)
            cache, sched._ring_dev = held[1], held[3]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"profile.{cfg['name']}.json"), "w") as f:
        json.dump(out, f, indent=1)
    for name, got in out.items():
        print(f"== {name}: {got['wall_ms']:.2f} ms a call on the host's "
              f"clock, {got['busy_ms']:.2f} ms of device operations")
        for op, ms in got["ops_ms"]:
            print(f"   {ms:8.3f}  {op[:110]}")
        for kernel, ms, share in got["kernel_roofline"]:
            print(f"   roofline {share:5.1f}%  {ms:8.3f} ms  {kernel}")
    backend.stop()


if __name__ == "__main__":
    main()

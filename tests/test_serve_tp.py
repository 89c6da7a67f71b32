"""Tensor-parallel serving end-to-end: the SERVE_TP path (engine + mesh
+ scheduler) on the conftest's 8 fake CPU devices.

The dryrun validates the model-level sharded forward; this covers what
it cannot: the scheduler's jitted serving programs (fused admission,
decode ticks, sampling state scatters, donation) running with
mesh-sharded params — the exact composition `SERVE_TP=N` deploys.
Oracle: the unsharded solo loop; outputs must match exactly (greedy).
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.parallel.mesh import MeshConfig, make_mesh
from p2p_llm_chat_tpu.parallel.sharding import shard_params
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

pytestmark = pytest.mark.model

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
STOP_IDS = set(CFG.eos_token_ids) | {TOK.eos_id}


def oracle(prompt: str, max_new: int) -> str:
    ids = TOK.encode(prompt, add_bos=True)
    cache = KVCache.create(CFG, 1, 128, jnp.float32)
    logits, cache = llama.prefill(PARAMS, CFG, jnp.asarray([ids]),
                                  jnp.asarray([len(ids)]), cache)
    last = np.asarray(logits[0, len(ids) - 1])
    out = []
    for _ in range(max_new):
        t = int(last.argmax())
        if t in STOP_IDS:
            break
        out.append(t)
        lg, cache = llama.decode_step(PARAMS, CFG, jnp.asarray([[t]]), cache)
        last = np.asarray(lg[0, 0])
    return TOK.decode(out)


def test_tp_engine_matches_unsharded_oracle():
    """Concurrent requests through a tp=2 engine (sharded params and
    pool) must be oracle-exact — sharding is a layout, not a model."""
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    sharded = shard_params(PARAMS, llama.param_axes(CFG), mesh)
    eng = TPUEngine(sharded, CFG, TOK, num_slots=2, max_seq=128,
                    mesh=mesh, page_size=16)
    try:
        prompts = ["tensor parallel", "serving check", "third request"]
        want = {p: oracle(p, 8) for p in prompts}
        got, errs = {}, []

        def worker(p):
            try:
                req = GenerateRequest(prompt=p, options=GenerateOptions(
                    max_tokens=8))
                got[p] = "".join(eng.generate_stream(req, RequestStats()))
            except Exception as e:   # noqa: BLE001
                errs.append((p, e))

        threads = [threading.Thread(target=worker, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errs, errs
        assert got == want
    finally:
        eng.stop()


def test_tp_engine_with_prefix_and_spec():
    """The full feature stack (prefix cache + speculation) composes with
    tensor parallelism — warmup compiles the sharded programs and the
    output stays oracle-exact."""
    from p2p_llm_chat_tpu.serve.engine import SUGGEST_PREFIX

    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    sharded = shard_params(PARAMS, llama.param_axes(CFG), mesh)
    eng = TPUEngine(sharded, CFG, TOK, num_slots=2, max_seq=256,
                    mesh=mesh, spec_k=3, prefix_texts=(SUGGEST_PREFIX,))
    try:
        eng.warmup(buckets=(64, 128))
        assert len(eng.scheduler._prefix) == 1
        p = SUGGEST_PREFIX + "see you at ten?"
        ids = TOK.encode(p, add_bos=True)
        cache = KVCache.create(CFG, 1, 256, jnp.float32)
        logits, cache = llama.prefill(PARAMS, CFG, jnp.asarray([ids]),
                                      jnp.asarray([len(ids)]), cache)
        last = np.asarray(logits[0, len(ids) - 1])
        out = []
        for _ in range(8):
            t = int(last.argmax())
            if t in STOP_IDS:
                break
            out.append(t)
            lg, cache = llama.decode_step(PARAMS, CFG, jnp.asarray([[t]]),
                                          cache)
            last = np.asarray(lg[0, 0])

        req = GenerateRequest(prompt=p, options=GenerateOptions(max_tokens=8))
        text = "".join(eng.generate_stream(req, RequestStats()))
        assert text == TOK.decode(out)
        assert eng.scheduler.metrics_snapshot()[
            "serve_prefix_admits_total"] == 1
    finally:
        eng.stop()


def test_tp_chunk_ladder_takes_one_committed_buffer():
    """A chunk ladder under a mesh: the admission's packed buffer goes
    up once, committed to every device of the mesh (so no chunk launch
    places it again), and the output stays oracle-exact."""
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    sharded = shard_params(PARAMS, llama.param_axes(CFG), mesh)
    eng = TPUEngine(sharded, CFG, TOK, num_slots=2, max_seq=256,
                    mesh=mesh, page_size=16, prefill_chunk=32)
    sched = eng.scheduler
    seen = []
    upload = sched._admit_upload
    sched._admit_upload = lambda *a, **k: (seen.append(upload(*a, **k))
                                           or seen[-1])
    try:
        p = "a prompt long enough to climb a ladder of chunks: " + "xyz " * 8
        assert 64 < len(TOK.encode(p, add_bos=True)) < 120
        req = GenerateRequest(prompt=p, options=GenerateOptions(max_tokens=8))
        text = "".join(eng.generate_stream(req, RequestStats()))
        assert text == oracle(p, 8)
        snap = sched.metrics_snapshot()
        assert snap["prefill_chunks_total"] == 4
        assert snap["serve_admit_uploads_total"] == 1 == len(seen)
        assert seen[0].committed and seen[0].sharding.is_fully_replicated
        assert seen[0].sharding.device_set == set(mesh.devices.flat)
    finally:
        eng.stop()


def test_tp_pool_and_fused_weights_are_sharded():
    """TP serving must actually PLACE the paged pool
    and the fused projections across the mesh — correctness alone
    (above) can hide silent replication, which breaks the memory-fit
    story that motivates TP. tiny-tp's 4 kv heads divide tp=2, so the
    sharded path (not the replication fallback) is what's asserted."""
    cfg = get_config("tiny-tp")
    params = llama.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    sharded = shard_params(params, llama.param_axes(cfg), mesh)
    eng = TPUEngine(sharded, cfg, ByteTokenizer(vocab_size=cfg.vocab_size),
                    num_slots=2, max_seq=128, mesh=mesh, page_size=16)
    try:
        sched = eng.scheduler
        # fused projections exist and shard over tp on the column axis
        wqkv = sched._params["layers"]["wqkv"]
        spec = wqkv.sharding.spec
        assert spec[-1] == "tp", f"wqkv replicated: {spec}"
        wgu = sched._params["layers"]["wgu"]
        assert wgu.sharding.spec[-1] == "tp"
        # paged pool shards over kv heads (dim 3 of [L, N, ps, Hkv, D])
        kspec = sched._cache.k.sharding.spec
        assert len(kspec) > 3 and kspec[3] == "tp", \
            f"KV pool replicated: {kspec}"
        # page table / lengths stay replicated (host-written per tick)
        assert sched._cache.page_table.sharding.is_fully_replicated
        # and the engine still serves through the sharded layout
        req = GenerateRequest(prompt="shard check",
                              options=GenerateOptions(max_tokens=4))
        text = "".join(eng.generate_stream(req, RequestStats()))
        assert isinstance(text, str)
    finally:
        eng.stop()

"""Fused multi-step decode: K scan steps in one dispatch must be
BIT-IDENTICAL to K sequential plain ticks (models/llama.decode_fused;
serve/scheduler.py decode_fuse_max).

Two layers of pinning:

- unit parity against a hand-rolled K-step loop of the exact plain-step
  ops (decode_step + sampling.sample_step_batched) — tokens, PRNG keys,
  penalty ring, cache contents and lengths all compared exactly, for
  dense, paged, and int8-quantized-pool caches, greedy and temperature
  sampling, including EOS landing mid-scan (the row must park inside
  the scan: length frozen, ring writes dropped, feed held);
- engine-level parity: the same requests through schedulers with
  fusion off vs on produce identical streams, and the adaptive-K
  decision table holds: a row within K tokens of a budget always
  collapses K to 1; pending admissions collapse K only with chunked
  prefill disabled (with chunking on — the default — every admission
  dispatch is bounded to one chunk, so fusion keeps ramping while a
  backlog drains; see test_fuse_k_policy_decision_table).

CPU-runnable by design (ci.sh runs this file under JAX_PLATFORMS=cpu);
interpret-mode Pallas covers the paged kernels.
"""

import queue

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.models.sampling import sample_step_batched
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler, _Slot
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)

B, K, RING, MAX_SEQ = 3, 4, 64, 64
# Per-row options exercising greedy (temp 0), temperature+top_p, and
# temperature+top_k+repeat_penalty in ONE batch — the fused scan must
# reproduce every sampler path, not just argmax.
TEMPS = jnp.asarray([0.0, 0.8, 0.6], jnp.float32)
TOP_KS = jnp.asarray([0, 0, 9], jnp.int32)
TOP_PS = jnp.asarray([1.0, 0.9, 1.0], jnp.float32)
RPS = jnp.asarray([1.0, 1.0, 1.2], jnp.float32)


def _sample_fn(logits, state, emit_pos, act):
    keys, ring = state
    toks, keys, ring = sample_step_batched(
        logits, keys, TEMPS, TOP_KS, TOP_PS, ring=ring, rp=RPS,
        emit_pos=emit_pos, active=act)
    return toks, (keys, ring)


@jax.jit
def _plain_step_dense(tokens, cache, active, keys, ring):
    """ONE plain tick, jitted — the scheduler's per-tick program shape
    (the parity claim is jitted-step vs jitted-scan, which is what
    serving actually runs; an eager loop drifts in f32 last bits)."""
    emit_pos = cache.lengths + 1
    logits, cache = llama.decode_step(PARAMS, CFG, tokens, cache,
                                      active=active, kv_window=MAX_SEQ)
    toks, keys, ring = sample_step_batched(
        logits[:, 0, :], keys, TEMPS, TOP_KS, TOP_PS, ring=ring, rp=RPS,
        emit_pos=emit_pos, active=active)
    tokens = jnp.where(active[:, None], toks[:, None], tokens)
    return toks, tokens, cache, keys, ring


@jax.jit
def _plain_step_paged(tokens, cache, active, keys, ring):
    emit_pos = cache.lengths + 1
    logits, cache = llama.decode_step_paged(
        PARAMS, CFG, tokens, cache, active=active, pages=MAX_SEQ // 16)
    toks, keys, ring = sample_step_batched(
        logits[:, 0, :], keys, TEMPS, TOP_KS, TOP_PS, ring=ring, rp=RPS,
        emit_pos=emit_pos, active=active)
    tokens = jnp.where(active[:, None], toks[:, None], tokens)
    return toks, tokens, cache, keys, ring


def _plain_loop(tokens, cache, active, keys, ring, stop, *, paged,
                pages=None):
    """K plain ticks through the jitted one-step program, with the
    host-side stop->park the scheduler applies between ticks."""
    step = _plain_step_paged if paged else _plain_step_dense
    outs, actives = [], []
    for _ in range(K):
        toks, tokens, cache, keys, ring = step(tokens, cache, active,
                                               keys, ring)
        outs.append(toks)
        actives.append(active)
        if len(stop):
            active = active & jnp.all(
                toks[:, None] != jnp.asarray(stop)[None, :], axis=1)
    return (jnp.stack(outs), jnp.stack(actives), tokens, cache, active,
            keys, ring)


def _dense_state():
    toks0 = jax.random.randint(jax.random.PRNGKey(1), (B, 8), 3,
                               CFG.vocab_size)
    lens = jnp.asarray([5, 8, 3], jnp.int32)
    cache = KVCache.create(CFG, B, MAX_SEQ, jnp.float32)
    logits, cache = llama.prefill(PARAMS, CFG, toks0, lens, cache)
    first = jnp.argmax(jnp.take_along_axis(
        logits, (lens - 1)[:, None, None], axis=1)[:, 0, :],
        -1).astype(jnp.int32)[:, None]
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray([11, 22, 33]))
    ring = jnp.full((B, RING), CFG.vocab_size, jnp.int32)
    return first, cache, keys, ring


def _paged_state(quantized):
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_batch)
    first, dense, keys, ring = _dense_state()
    ps = 16
    mppr = MAX_SEQ // ps
    cache = PagedKVCache.create(CFG, B, B * mppr + 1, ps,
                                max_pages_per_row=mppr, dtype=jnp.float32,
                                quantized=quantized)
    tables = (1 + np.arange(B * mppr, dtype=np.int32)).reshape(B, mppr)
    cache = write_prefill_batch(cache, dense.k, dense.v,
                                jnp.arange(B, dtype=jnp.int32),
                                dense.lengths, jnp.asarray(tables))
    return first, cache, keys, ring


def _run_both(first, cache, keys, ring, stop, *, paged, pages=None):
    active = jnp.ones((B,), bool)
    plain = _plain_loop(first, cache, active, keys, ring, stop,
                        paged=paged, pages=pages)
    kwargs = dict(num_steps=K, sample_fn=_sample_fn,
                  sample_state=(keys, ring), stop_ids=stop, active=active)
    if paged:
        kwargs.update(pages=pages)
    else:
        kwargs.update(kv_window=MAX_SEQ)
    fused = jax.jit(
        lambda t, c: llama.decode_fused(PARAMS, CFG, t, c, **kwargs)
    )(first, cache)
    return plain, fused


def _assert_parity(plain, fused, stop_used):
    (p_toks, p_act, p_next, p_cache, p_active, p_keys, p_ring) = plain
    (f_toks, f_emit, f_next, f_cache, f_active, (f_keys, f_ring)) = fused
    assert np.array_equal(np.asarray(p_act), np.asarray(f_emit))
    # Emitted positions (row live at that step) must agree token-exactly;
    # post-park positions are garbage on both sides by contract.
    em = np.asarray(p_act)
    tp, tf = np.asarray(p_toks), np.asarray(f_toks)
    assert np.array_equal(tp[em], tf[em])
    assert np.array_equal(np.asarray(p_active), np.asarray(f_active))
    assert np.array_equal(np.asarray(p_next), np.asarray(f_next))
    assert np.array_equal(np.asarray(p_keys), np.asarray(f_keys))
    assert np.array_equal(np.asarray(p_ring), np.asarray(f_ring))
    assert np.array_equal(np.asarray(p_cache.lengths),
                          np.asarray(f_cache.lengths))
    if not stop_used:
        # No mid-scan park: every write is live on both paths, so the
        # caches must match bit-for-bit (parked paths differ only in
        # never-trusted slots, which scatter garbage by design).
        assert np.array_equal(np.asarray(p_cache.k), np.asarray(f_cache.k))
        assert np.array_equal(np.asarray(p_cache.v), np.asarray(f_cache.v))


@pytest.mark.parametrize("mode", ["dense", "paged", "paged-int8"])
def test_fused_k_steps_bit_identical_to_plain_ticks(mode):
    if mode == "dense":
        first, cache, keys, ring = _dense_state()
        pages = None
    else:
        first, cache, keys, ring = _paged_state(quantized=(mode ==
                                                           "paged-int8"))
        pages = MAX_SEQ // 16
    stop = np.zeros((0,), np.int32)
    plain, fused = _run_both(first, cache, keys, ring, stop,
                             paged=pages is not None, pages=pages)
    _assert_parity(plain, fused, stop_used=False)

    # EOS mid-scan: stop on the token the greedy row emitted at step 1,
    # so the park lands strictly inside the fusion window. The fused
    # scan must freeze that row exactly where the host-side release
    # would have between two plain ticks.
    stop = np.asarray([int(np.asarray(plain[0])[1, 0])], np.int32)
    plain2, fused2 = _run_both(first, cache, keys, ring, stop,
                               paged=pages is not None, pages=pages)
    _assert_parity(plain2, fused2, stop_used=True)
    assert not np.asarray(fused2[4])[0], "greedy row should have parked"
    assert np.asarray(fused2[3].lengths)[0] < np.asarray(
        plain[3].lengths)[0], "parked row's length must freeze mid-scan"


def _mk_slot(max_new=100, n_ids=0, ctx_len=10, ctx_budget=60) -> _Slot:
    s = _Slot(req=GenerateRequest(prompt="x"), stats=None,
              out_q=queue.Queue(), seed=0)
    s.max_new, s.ctx_len, s.ctx_budget = max_new, ctx_len, ctx_budget
    s.ids = list(range(n_ids))
    return s


def _policy_probe(prefill_chunk, max_seq=MAX_SEQ):
    """A scheduler whose loop thread is already joined: _choose_fuse_k
    is probed as a pure policy function, so planting fake pending work
    (a bare sentinel in _admit_q, a bodiless carry slot) can't race the
    live loop's admission path, which would try to admit it."""
    sched = BatchScheduler(PARAMS, CFG, TOK, num_slots=2, max_seq=max_seq,
                           decode_fuse_max=4, prefill_chunk=prefill_chunk)
    sched.stop()
    return sched


def test_fuse_k_policy_decision_table():
    """Pin the fused-K decision table (scheduler._choose_fuse_k):

    | prefill_chunk          | pending admission          | near-budget row | K     |
    |------------------------|----------------------------|-----------------|-------|
    | on, divides max_seq    | queued / carried / waiting | no              | ramps |
    | on                     | any                        | yes             | 1     |
    | on, max_seq % C != 0   | queued / carried / waiting | no              | 1     |
    | off (0)                | queued / carried / waiting | no              | 1     |
    | off                    | none                       | no              | ramps |

    With chunking on, a backlog must NOT collapse K: every admission
    dispatch is already bounded to one chunk's compute, so fusion keeps
    amortising host dispatch while the backlog drains (the pre-chunking
    rule held decode at K=1 for an entire drain). Only near-budget rows
    (test_adaptive_k_respects_row_budgets) and live speculation — K=1
    at the dispatch site via _dispatch_tick(allow_fuse=False) — still
    defuse. With chunking off, the legacy whole-bucket prefill follows
    the tick, so any pending admission collapses K and resets the ramp.
    """
    chunked = _policy_probe(prefill_chunk=64)
    chunked._slots[0] = _mk_slot()
    for plant, clear in (
            (lambda: chunked._admit_q.put(object()),
             lambda: chunked._admit_q.get_nowait()),
            (lambda: chunked._admit_carry.append(_mk_slot()),
             lambda: chunked._admit_carry.clear()),
            (lambda: chunked._waiting.append(_mk_slot()),
             lambda: chunked._waiting.clear())):
        plant()
        chunked._fuse_ramp = 1
        # Pending admission alone: K keeps ramping 2 -> 4, holds at cap.
        assert chunked._choose_fuse_k(0) == 2
        assert chunked._choose_fuse_k(0) == 4
        assert chunked._choose_fuse_k(0) == 4
        # ...but a near-budget row still collapses K to 1.
        chunked._slots[1] = _mk_slot(ctx_len=59, ctx_budget=60)
        assert chunked._choose_fuse_k(0) == 1
        chunked._slots[1] = None
        clear()

    # Chunking on but max_seq NOT a chunk multiple: the capped top
    # bucket admits single-shot whole-bucket, so a pending admission may
    # hide an unbounded prefill — the legacy collapse rule applies
    # (conservative across all buckets in that config).
    capped = _policy_probe(prefill_chunk=64, max_seq=200)
    capped._slots[0] = _mk_slot()
    capped._fuse_ramp = 4
    capped._admit_q.put(object())
    assert capped._choose_fuse_k(0) == 1
    capped._admit_q.get_nowait()
    assert capped._choose_fuse_k(0) == 2

    single = _policy_probe(prefill_chunk=0)
    single._slots[0] = _mk_slot()
    # Chunking off: a queued request collapses K and resets the ramp.
    single._fuse_ramp = 4
    single._admit_q.put(object())
    assert single._choose_fuse_k(0) == 1
    single._admit_q.get_nowait()
    assert single._choose_fuse_k(0) == 2
    # Carried admission chunks and page-starved waiters also collapse.
    single._admit_carry = [_mk_slot()]
    assert single._choose_fuse_k(0) == 1
    single._admit_carry = []
    single._waiting = [_mk_slot()]
    assert single._choose_fuse_k(0) == 1
    single._waiting = []
    # Clear: K ramps 2 -> 4 and holds at the cap.
    assert single._choose_fuse_k(0) == 2
    assert single._choose_fuse_k(0) == 4
    assert single._choose_fuse_k(0) == 4


def test_adaptive_k_respects_row_budgets():
    sched = BatchScheduler(PARAMS, CFG, TOK, num_slots=2, max_seq=MAX_SEQ,
                           decode_fuse_max=4)
    try:
        # A row within K tokens of max_new: collapse to 1.
        sched._slots[0] = _mk_slot(max_new=8, n_ids=7)
        assert sched._choose_fuse_k(0) == 1
        # A row within K tokens of its KV budget: collapse to 1.
        sched._slots[0] = _mk_slot(ctx_len=59, ctx_budget=60)
        assert sched._choose_fuse_k(0) == 1
        # In-flight pipelined steps count against the headroom.
        sched._slots[0] = _mk_slot(max_new=10, n_ids=5)
        assert sched._choose_fuse_k(4) == 1
        assert sched._choose_fuse_k(0) == 2
        # Headroom for 2 but not 4: K clamps to the ladder's 2.
        sched._slots[0] = _mk_slot(max_new=8, n_ids=5)
        sched._fuse_ramp = 4
        assert sched._choose_fuse_k(0) == 2
    finally:
        sched.stop()


def test_engine_stream_identical_with_fusion_on():
    """End-to-end: same seeds through fusion-off and fusion-on
    schedulers -> identical text, and the fused scheduler actually
    fused (metrics engage)."""
    off = BatchScheduler(PARAMS, CFG, TOK, num_slots=2, max_seq=128,
                         decode_fuse_max=1)
    on = BatchScheduler(PARAMS, CFG, TOK, num_slots=2, max_seq=128,
                        decode_fuse_max=4)
    try:
        for opts in (GenerateOptions(max_tokens=10),
                     GenerateOptions(max_tokens=10, temperature=0.8,
                                     top_p=0.9, seed=5)):
            req = GenerateRequest(prompt="fused parity", options=opts)
            a = "".join(off.submit(req, RequestStats()))
            b = "".join(on.submit(
                GenerateRequest(prompt="fused parity", options=opts),
                RequestStats()))
            assert a == b
        snap = on.metrics_snapshot()
        assert snap["decode_fused_ticks_total"] > 0
        assert snap["decode_fused_mean_k"] > 1.0
        assert off.metrics_snapshot()["decode_fused_ticks_total"] == 0
    finally:
        off.stop()
        on.stop()

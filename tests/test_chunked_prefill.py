"""Chunked prefill parity: a prompt admitted in fixed token-budget
chunks (serve/scheduler.py ``prefill_chunk``) must be BIT-identical to
the single-shot whole-bucket admission — same first-token sample, same
token stream, same cache contents.

Three layers of pinning, mirroring tests/test_fused_decode.py:

- model-level: ``llama.prefill_chunk`` continuation forwards over the
  chunk ladder vs ONE ``llama.prefill`` of the whole prompt — cache k/v
  and each row's last-prompt-position logits compared exactly (the
  full-width-mask rule: every chunk attends the same padded KV width as
  the single shot, so XLA's reduction blocking cannot drift last bits);
- ops-level: per-chunk ``write_prefill_chunk`` splices vs one
  ``write_prefill_batch`` — pool bits compared exactly for page-aligned
  chunks, sub-page chunks, a chunk boundary landing MID-page, and an
  unaligned (prefix-offset) start, on bf16 and int8-quantized pools
  (int8 stays exact because scales are per-token over head_dim: a
  token's quantization never depends on which dispatch wrote it);
- scheduler-level: the same requests through a chunked
  (``prefill_chunk=32``) and a single-shot (``prefill_chunk=0``)
  scheduler produce identical streams across page sizes x int8-KV x
  prefix-cache hit and miss, the chunked scheduler actually chunked
  (``prefill_chunks_total`` advances), and warmup pre-compiles the
  whole continuation ladder so no chunk program compiles mid-serving.

CPU-runnable by design; ci.sh runs this file on a SINGLE-device CPU
(`xla_force_host_platform_device_count=1`) — that is the bit-exact
reference platform. Under the suite's default 8-virtual-device topology
(conftest.py, the sharding-simulation environment) XLA:CPU partitions
in-program reductions across a per-device thread-pool slice whose split
depends on the dispatch's query width, so the whole-prompt and chunk
forwards drift by 1 ulp from layer 1 on — a platform scheduling
artifact, not a model one (verified: the same comparison is exactly
equal at any chunk size on 1 device, and no flag short of matching
dispatch shapes removes it on 8). The model-level exact asserts
therefore skip when more than one device is visible; the ops-level
splice parity (pure scatters, no reductions) and the scheduler-level
stream parity run — and must pass — on every topology.

Interpret-mode Pallas covers the paged kernels.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache, write_prefill_batch,
                                           write_prefill_chunk)
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)

MAX_SEQ = 256
CHUNK = 32
# > 1 chunk (bucket 64) and > 3 chunks (bucket 128) respectively, so
# both the 2-dispatch and the first/mid/final program shapes run.
PROMPT_2CH = "Draft a short reply to: are we still on for ten?"
PROMPT_4CH = ("Summarize the following discussion thread about quarterly "
              "planning, the picnic schedule, and the office move into "
              "one sentence:")


# -- model-level: continuation-chunk forwards == one whole-prompt prefill

_exact_platform = pytest.mark.skipif(
    jax.device_count() > 1,
    reason="bit-exact model parity needs the single-device CPU topology "
           "(ci.sh's dedicated invocation); the 8-virtual-device suite "
           "splits reductions by query width -> 1 ulp drift")


@_exact_platform
def test_model_chunk_ladder_bit_identical_to_single_prefill():
    B, S, W, C = 3, 64, 96, 16
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 3,
                              CFG.vocab_size)
    lens = jnp.asarray([50, 64, 17], jnp.int32)   # last position in
    # chunk 3, chunk 3 (exact end), chunk 1 — the keep-mask merge must
    # pick each row's logits from ITS chunk only.
    single = KVCache.create(CFG, B, W, dtype=jnp.float32)
    logits_s, single = llama.prefill(PARAMS, CFG, toks, lens, single,
                                     last_only=True)

    chunked = KVCache.create(CFG, B, W, dtype=jnp.float32)
    merged = jnp.zeros((B, CFG.vocab_size), jnp.float32)
    for off in range(0, S, C):
        local_last = lens - 1 - off
        lg, chunked = llama.prefill_chunk(
            PARAMS, CFG, toks[:, off: off + C], chunked, off,
            last_idx=jnp.clip(local_last, 0, C - 1))
        keep = (local_last >= 0) & (local_last < C)
        merged = jnp.where(keep[:, None], lg[:, 0, :], merged)

    np.testing.assert_array_equal(np.asarray(single.k),
                                  np.asarray(chunked.k))
    np.testing.assert_array_equal(np.asarray(single.v),
                                  np.asarray(chunked.v))
    np.testing.assert_array_equal(np.asarray(logits_s[:, 0, :]),
                                  np.asarray(merged))


@_exact_platform
def test_model_chunk_resumes_mid_prompt_after_prefix():
    """A chunk starting at an arbitrary (non-power-of-two) offset — the
    prefix-continuation shape — must emit the same KV the whole-prompt
    forward wrote at those positions."""
    B, S, W, P0 = 2, 48, 80, 19
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, P0 + S), 3,
                              CFG.vocab_size)
    lens = jnp.full((B,), P0 + S, jnp.int32)
    single = KVCache.create(CFG, B, W, dtype=jnp.float32)
    _, single = llama.prefill(PARAMS, CFG, toks, lens, single,
                              last_only=True)

    chunked = KVCache.create(CFG, B, W, dtype=jnp.float32)
    _, chunked = llama.prefill_chunk(PARAMS, CFG, toks[:, :P0], chunked, 0)
    for off in range(P0, P0 + S, 16):
        _, chunked = llama.prefill_chunk(
            PARAMS, CFG, toks[:, off: off + 16], chunked, off)
    np.testing.assert_array_equal(np.asarray(single.k),
                                  np.asarray(chunked.k))
    np.testing.assert_array_equal(np.asarray(single.v),
                                  np.asarray(chunked.v))


# -- ops-level: per-chunk pool splice == whole-prompt pool splice


def _paged_state(quantized, *, page_size=16, S=64, R=3):
    pool_pages = R * (S // page_size) + 4
    cache = PagedKVCache.create(CFG, batch=4, num_pages=pool_pages,
                                page_size=page_size,
                                max_pages_per_row=S // page_size + 1,
                                dtype=jnp.bfloat16, quantized=quantized)
    key = jax.random.PRNGKey(7)
    k = jax.random.normal(key, (CFG.num_layers, R, S, CFG.num_kv_heads,
                                CFG.head_dim), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape, jnp.bfloat16)
    rows = jnp.asarray(list(range(R)), jnp.int32)
    lens = jnp.asarray([S, S - 5, S - page_size + 3], jnp.int32)
    mppr = cache.page_table.shape[1]
    tables = np.zeros((R, mppr), np.int32)
    for r in range(R):
        n = -(-int(lens[r]) // page_size)
        tables[r, :n] = 1 + r * (S // page_size) + np.arange(n)
    tables = jnp.asarray(tables)
    return cache, k, v, rows, lens, tables


def _pool_bits(cache):
    out = [np.asarray(cache.k), np.asarray(cache.v)]
    if cache.quantized:
        out += [np.asarray(cache.k_scale), np.asarray(cache.v_scale)]
    return out


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("C", [16, 32, 8], ids=["page", "2page", "midpage"])
def test_write_prefill_chunk_matches_batch_splice(quantized, C):
    """Chunk ladder splices (page-aligned, multi-page, and sub-page —
    the mid-page boundary) reproduce the one-shot batch splice bit for
    bit, including the final table/length install."""
    cache, k, v, rows, lens, tables = _paged_state(quantized)
    S = k.shape[2]
    single = write_prefill_batch(cache, k, v, rows, lens, tables)

    chunked = cache
    for off in range(0, S, C):
        chunked = write_prefill_chunk(chunked, k[:, :, off: off + C],
                                      v[:, :, off: off + C], tables, off)
    chunked = chunked._replace(
        page_table=chunked.page_table.at[rows].set(tables.astype(jnp.int32),
                                                   mode="drop"),
        lengths=chunked.lengths.at[rows].set(
            lens.astype(chunked.lengths.dtype), mode="drop"))

    for a, b in zip(_pool_bits(single), _pool_bits(chunked)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(single.page_table),
                                  np.asarray(chunked.page_table))
    np.testing.assert_array_equal(np.asarray(single.lengths),
                                  np.asarray(chunked.lengths))


def test_write_prefill_chunk_unaligned_start():
    """A prefix-offset splice (start mid-page, the broadcast-prefix
    continuation) lands each token at its page/slot exactly as the
    aligned whole write would."""
    cache, k, v, rows, lens, tables = _paged_state(False)
    S = k.shape[2]
    whole = write_prefill_chunk(cache, k, v, tables, 0)
    split = write_prefill_chunk(cache, k[:, :, :21], v[:, :, :21],
                                tables, 0)
    split = write_prefill_chunk(split, k[:, :, 21:], v[:, :, 21:],
                                tables, 21)
    for a, b in zip(_pool_bits(whole), _pool_bits(split)):
        np.testing.assert_array_equal(a, b)


# -- scheduler-level: chunked vs single-shot admission, end to end


def _mk_sched(chunk, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("decode_fuse_max", 1)
    return BatchScheduler(PARAMS, CFG, TOK, prefill_chunk=chunk, **kw)


def _run(sched, prompt, opts):
    return "".join(sched.submit(GenerateRequest(prompt=prompt, options=opts),
                                RequestStats()))


OPTS = (GenerateOptions(max_tokens=8),
        GenerateOptions(max_tokens=8, temperature=0.8, top_p=0.9, seed=5))

SCHED_MODES = {
    "paged": {"page_size": 16},
    "paged-int8": {"page_size": 16, "kv_quant": True},
    # page_size > chunk: the second chunk's splice starts MID-page (the
    # per-token scatter path) on the live scheduler, not just in the
    # ops-level unit test.
    "paged-midpage": {"page_size": 64},
    "paged-midpage-int8": {"page_size": 64, "kv_quant": True},
}


@pytest.mark.parametrize("mode", SCHED_MODES, ids=list(SCHED_MODES))
def test_scheduler_stream_identical_chunked_vs_single_shot(mode):
    chunked = _mk_sched(CHUNK, **SCHED_MODES[mode])
    single = _mk_sched(0, **SCHED_MODES[mode])
    try:
        for prompt in (PROMPT_2CH, PROMPT_4CH):
            for opts in OPTS:
                assert _run(chunked, prompt, opts) == \
                    _run(single, prompt, opts)
        snap = chunked.metrics_snapshot()
        # 2 chunks for the 64 bucket + 4 for the 128 bucket, per opts.
        assert snap["prefill_chunks_total"] == 2 * (2 + 4)
        assert single.metrics_snapshot()["prefill_chunks_total"] == 0
        for key in ("decode_stall_ms", "inter_token_p50_ms",
                    "inter_token_p95_ms"):
            assert key in snap
    finally:
        chunked.stop()
        single.stop()


@pytest.mark.parametrize("mode", ["paged", "paged-int8"])
def test_scheduler_prefix_hit_and_miss_parity(mode):
    """Prefix-cache hit (suffix-continuation chunks resume at the
    prefix's non-power-of-two offset) and miss both stream identically
    to the single-shot scheduler."""
    head = "template head, shared by every request in the fleet: "
    hit = head + PROMPT_4CH
    chunked = _mk_sched(CHUNK, prefix_cache=True, **SCHED_MODES[mode])
    single = _mk_sched(0, prefix_cache=True, **SCHED_MODES[mode])
    try:
        assert chunked.register_prefix(head) > 0
        assert single.register_prefix(head) > 0
        for prompt in (hit, PROMPT_4CH):
            for opts in OPTS:
                assert _run(chunked, prompt, opts) == \
                    _run(single, prompt, opts)
        for s in (chunked, single):
            snap = s.metrics_snapshot()
            assert snap["serve_prefix_admits_total"] == len(OPTS)
        assert chunked.metrics_snapshot()["prefill_chunks_total"] > 0
    finally:
        chunked.stop()
        single.stop()


def test_reset_decode_stall_served_while_batch_is_full():
    """reset_decode_stall must be serviced while every slot is busy
    decoding (regression: as a queued admission job it starved behind a
    full batch — admission never drains the queue with no free rows —
    and timed out on a healthy scheduler)."""
    sched = _mk_sched(CHUNK, num_slots=1)
    try:
        out: list[str] = []
        th = threading.Thread(target=lambda: out.append(
            _run(sched, PROMPT_2CH, GenerateOptions(max_tokens=128))))
        th.start()
        deadline = time.monotonic() + 30
        while (time.monotonic() < deadline
               and sched.metrics_snapshot()["serve_batch_occupancy"] < 1):
            time.sleep(0.01)
        sched.reset_decode_stall(timeout_s=10.0)
        assert sched.metrics_snapshot()["decode_stall_ms"] == 0.0
        th.join()
        assert out and out[0]
    finally:
        sched.stop()


def test_non_multiple_top_bucket_falls_back_to_single_shot():
    """max_seq caps the top serving bucket at max_seq itself, which need
    not be a multiple of the chunk width (here 80 vs CHUNK=32) — that
    bucket must admit single-shot (output-identical by contract), and
    warmup must compile no ladder for it. Regression: a ladder whose
    offsets step 0/32/64 past S=80 has no final chunk, so the admission
    dispatched continuation chunks forever (hung request, one fresh
    compile per unbounded offset). The warmup assert runs first so the
    broken world fails fast instead of hanging in _run."""
    prompt = PROMPT_4CH[:70]                    # 71 tokens -> the 80 bucket
    chunked = _mk_sched(CHUNK, max_seq=80)
    single = _mk_sched(0, max_seq=80)
    try:
        chunked.warmup(prompt_buckets=(80,), windows=(80,))
        single.warmup(prompt_buckets=(80,), windows=(80,))
        assert not any(S == 80 for _, S, _, _ in
                       chunked._prefill_chunk_programs)
        for opts in OPTS:
            assert _run(chunked, prompt, opts) == _run(single, prompt, opts)
        assert chunked.metrics_snapshot()["prefill_chunks_total"] == 0
    finally:
        chunked.stop()
        single.stop()


def test_warmup_compiles_the_chunk_ladder():
    """Warmup must walk every continuation-chunk offset of each bucket
    above the chunk budget (a lazy chunk compile mid-admission would
    stall every live stream — the exact failure chunking exists to
    remove), and live admissions must then add no new programs."""
    sched = _mk_sched(CHUNK)
    try:
        sched.warmup(prompt_buckets=(64, 128), windows=(128,))
        keys = set(sched._prefill_chunk_programs)
        assert {(0, 64, off, CHUNK) for off in range(0, 64, CHUNK)} <= keys
        assert {(0, 128, off, CHUNK) for off in range(0, 128, CHUNK)} <= keys
        _run(sched, PROMPT_4CH, OPTS[0])
        assert set(sched._prefill_chunk_programs) == keys
        assert sched.metrics_snapshot()["prefill_chunks_total"] == 4
    finally:
        sched.stop()

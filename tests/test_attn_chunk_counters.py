"""The flash-append kernel's walk, counted where it is dispatched.

``serve_attn_chunks_total`` / ``serve_attn_chunks_walked_total``
(serve/scheduler.py ``_note_attn_chunks``): for decode dispatches whose
window runs the flash-append kernel, the (row, chunk) programs of its
grid at every step, and those whose chunk starts inside its row's
context, which are the only ones the kernel fetches and folds
(ops/paged_attention.py ``holds_rows``). Host arithmetic with the
kernel's own chunk size (``flash_append_chunk_pages``); on the CPU the
kernel never engages (``paged_flash_min_w`` 0), so the served test opens
the gauge by hand: the programs still take the gather path here, and the
counters say what the kernel would have been asked.
"""

import importlib
import re
import time
import types
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.serve.api import OllamaServer
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

pa = importlib.import_module("p2p_llm_chat_tpu.ops.paged_attention")

CFG = get_config("tiny")
TOTAL, WALKED = "serve_attn_chunks_total", "serve_attn_chunks_walked_total"
PS, SLOTS = 16, 4


@pytest.fixture(scope="module")
def engine():
    eng = TPUEngine(llama.init_params(CFG, jax.random.PRNGKey(0),
                                      dtype=jnp.float32),
                    CFG, ByteTokenizer(vocab_size=CFG.vocab_size),
                    num_slots=SLOTS, max_seq=256, page_size=PS,
                    kv_quant=True, decode_fuse_max=4)
    yield eng
    eng.stop()


def _ct(window: int) -> int:
    """The kernel's chunk, in tokens, for the fixture's int8 pool."""
    return PS * pa.flash_append_chunk_pages(CFG.kv_dim, 1, PS, window // PS)


# (contexts of the four rows, None = free; K; inflight) at window 256,
# whose int8 chunk at tiny's hd = 64 is the whole window unless the
# budget is shrunk: the fixture below shrinks it to 64 tokens, 4 chunks.
_CASES = {
    "every-row-fills-the-window": ([255, 255, 255, 255], 1, 0),
    "all-free": ([None] * 4, 4, 0),
    "ragged": ([0, 1, 64, 65], 1, 0),
    "chunk-edges": ([63, 64, 127, 129], 1, 0),
    "fused-steps-cross-a-chunk": ([62, None, None, 200], 4, 0),
    "inflight-counts": ([62, None, 10, None], 2, 2),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_note_attn_chunks_counts_the_kernels_walk(case, engine, monkeypatch):
    ctxs, K, inflight = _CASES[case]
    sched = engine.scheduler
    # 64 int8 tokens a chunk at hd = 64: 4 chunks in a 256-token window.
    monkeypatch.setattr(pa, "_FLASH_CHUNK_TOK_BYTES", 4)
    assert _ct(256) == 64
    monkeypatch.setattr(sched, "_slots", [
        None if n is None else types.SimpleNamespace(ctx_len=n)
        for n in ctxs])
    monkeypatch.setattr(sched, "_n_attn_chunks", 0)
    monkeypatch.setattr(sched, "_n_attn_chunks_walked", 0)
    sched._note_attn_chunks(256, K, inflight)
    total, walked = sched._n_attn_chunks, sched._n_attn_chunks_walked
    assert total == SLOTS * 4 * K
    want = sum(min(4, -(-(n + inflight + j) // 64))
               for n in ctxs if n is not None for j in range(K))
    assert walked == want <= total
    if case == "every-row-fills-the-window":
        assert walked == total
    if case == "all-free":
        assert walked == 0
    if case == "ragged":
        assert walked == 0 + 1 + 1 + 2


def test_counters_move_only_where_the_kernel_runs_and_reach_metrics(engine):
    sched = engine.scheduler

    def generate(n: int) -> None:
        req = GenerateRequest(prompt="x" * 70,
                              options=GenerateOptions(max_tokens=n))
        stats = RequestStats()
        "".join(engine.generate_stream(req, stats))
        assert stats.completion_tokens == n

    def settled() -> dict:
        # The last dispatch is counted when it is sent, one iteration
        # ahead of the tokens: wait for the loop to go quiet.
        last = None
        for _ in range(200):
            m = engine.metrics_snapshot()
            if last is not None and m[TOTAL] == last[TOTAL] and (
                    m["serve_batch_occupancy"] == 0):
                return m
            last = m
            time.sleep(0.05)
        return m

    # The CPU cannot run the kernel: the gauge is 0 and nothing counts.
    assert sched._paged_flash_min_w == 0
    generate(6)
    m = settled()
    assert m[TOTAL] == 0 and m[WALKED] == 0
    # Gauge opened at 128: a prompt of about 70 tokens decodes in the
    # 128 window (one chunk a row at the real budget), so each step
    # asks for SLOTS programs and the one live row walks one.
    saved = sched._paged_flash_min_w
    sched._paged_flash_min_w = 128
    try:
        generate(9)
        m = settled()
    finally:
        sched._paged_flash_min_w = saved
    steps = m[TOTAL] // SLOTS
    assert steps >= 8 and m[TOTAL] == steps * SLOTS
    assert 0 < m[WALKED] <= m[TOTAL]
    assert m[WALKED] <= steps          # one live row, one chunk a window
    srv = OllamaServer(engine, addr="127.0.0.1:0").start()
    try:
        with urllib.request.urlopen(f"{srv.url}/metrics", timeout=10) as r:
            text = r.read().decode()
    finally:
        srv.stop()
    for name in (TOTAL, WALKED):
        assert f"# TYPE {name} counter" in text
        assert re.search(rf"^{name} {m[name]}$", text, re.M)

"""Serving chaos test: a randomized concurrent workload — mixed prompt
lengths, sampled and greedy rows, mid-stream cancellations, tiny paged
pools, speculation on — must never deadlock, never wedge a consumer, and
every completed greedy request must still match the solo oracle.

This is the insurance policy over the scheduler's moving parts
(pipelined ticks, spec ticks with pipeline flushes, adaptive throttle,
page backpressure, queue deadline): whatever interleaving the threads
produce, the outputs and liveness contracts hold.
"""

import random
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

pytestmark = pytest.mark.model

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
STOP_IDS = set(CFG.eos_token_ids) | {TOK.eos_id}
MAX_SEQ = 64


def greedy_oracle(prompt: str, max_new: int) -> str:
    """Solo loop with the engine's exact budget/stop rules."""
    ids = TOK.encode(prompt, add_bos=True)
    if len(ids) > MAX_SEQ - 2:
        ids = ids[-(MAX_SEQ - 2):]
    budget = MAX_SEQ - 1 - len(ids)
    max_new = max(1, min(max_new, budget))
    cache = KVCache.create(CFG, 1, MAX_SEQ, jnp.float32)
    logits, cache = llama.prefill(PARAMS, CFG, jnp.asarray([ids]),
                                  jnp.asarray([len(ids)]), cache)
    last = np.asarray(logits[0, len(ids) - 1])
    out, ctx = [], len(ids)
    for _ in range(max_new):
        t = int(last.argmax())
        if t in STOP_IDS:
            break
        out.append(t)
        ctx += 1
        if ctx + 1 >= MAX_SEQ:               # engine context-full rule
            break
        lg, cache = llama.decode_step(PARAMS, CFG, jnp.asarray([[t]]), cache)
        last = np.asarray(lg[0, 0])
    return TOK.decode(out)


def test_chaos_workload_liveness_and_greedy_correctness():
    rng = random.Random(7)
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=3, max_seq=MAX_SEQ,
                    page_size=16, num_pages=10, spec_k=3, queue_timeout_s=120.0)
    N = 24
    prompts = [("ab " * rng.randrange(1, 20)).strip() for _ in range(N)]
    max_toks = [rng.randrange(1, 20) for _ in range(N)]
    results: dict = {}
    errors: dict = {}

    def worker(i):
        greedy = i % 3 != 2                  # two thirds greedy
        cancel = i % 5 == 4                  # every 5th cancels mid-stream
        opts = (GenerateOptions(max_tokens=max_toks[i]) if greedy else
                GenerateOptions(max_tokens=max_toks[i], temperature=0.8,
                                top_p=0.9, seed=i))
        req = GenerateRequest(prompt=prompts[i], options=opts)
        it = eng.generate_stream(req, RequestStats())
        try:
            if cancel:
                try:
                    next(it)
                except StopIteration:
                    pass
                it.close()
                results[i] = None            # cancelled: no output contract
                return
            results[i] = ("greedy" if greedy else "sampled", "".join(it))
        except RuntimeError as e:
            errors[i] = str(e)

    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        stuck = [i for i, t in enumerate(threads) if t.is_alive()]
        assert not stuck, f"consumers wedged: {stuck}"
        assert not errors, errors            # deadline is far beyond this load
        checked = 0
        for i, r in results.items():
            if r is None or r[0] != "greedy":
                continue
            assert r[1] == greedy_oracle(prompts[i], max_toks[i]), (
                i, prompts[i])
            checked += 1
        assert checked >= N // 2             # most requests completed
    finally:
        eng.stop()

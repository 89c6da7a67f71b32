"""Continuous-batching engine tests (CPU, tiny random model).

The correctness oracle for batching: any request served through the shared
fixed-shape batched decode loop must produce exactly the tokens a solo
batch=1 prefill+decode loop produces for the same prompt — regardless of
what other requests are in flight, in which slots, or in what order
(parked rows, ragged lengths, slot reuse must all be invisible).
"""

import threading
import time

import pytest

import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

import jax

from solo import Solo, generate as run

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
STOP_IDS = set(CFG.eos_token_ids) | {TOK.eos_id}


# The solo batch=1 greedy loop with the engine's stop rule (tests/solo.py)
# on the model layer's plain cache, and the same loop on a one-row int8
# pool: the exact reference of an engine whose pool is int8.
SOLO = {False: Solo(llama, CFG, TOK),
        True: Solo(llama, CFG, TOK, pool="int8")}


def oracle(prompt: str, max_new: int, kv_quant: bool = False) -> str:
    return SOLO[kv_quant](PARAMS, prompt, max_new)


@pytest.fixture(scope="module", params=["paged", "paged-int8"])
def engine(request):
    """Every oracle test runs against the pool as floats and as int8
    (Pallas kernels in interpret mode on CPU)."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=3, max_seq=128,
                    page_size=16, kv_quant=request.param == "paged-int8")
    yield eng
    eng.stop()


def want(engine, prompt: str, max_new: int) -> str:
    """The oracle of the leg ``engine`` is."""
    return oracle(prompt, max_new, kv_quant=engine.scheduler.kv_quant)


def test_single_request_matches_oracle(engine):
    text, stats = run(engine, "hello world", max_tokens=12)
    assert text == want(engine, "hello world", 12)
    assert stats.prompt_tokens == len(TOK.encode("hello world", add_bos=True))
    assert stats.ttft_s is not None and stats.total_s is not None
    assert stats.total_s >= stats.ttft_s


def test_repeat_is_deterministic_greedy(engine):
    a, _ = run(engine, "determinism", max_tokens=10)
    b, _ = run(engine, "determinism", max_tokens=10)
    assert a == b


def test_concurrent_requests_each_match_solo_run(engine):
    """6 requests through 3 slots: concurrency, ragged prompt lengths,
    admission mid-decode, and slot reuse must not change any output."""
    prompts = ["a", "bb longer prompt here", "ccc", "d d d d",
               "a completely different prompt", "short"]
    expected = {p: want(engine, p, 10) for p in prompts}
    got = {}
    errs = []

    def worker(p):
        try:
            text, _ = run(engine, p, max_tokens=10)
            got[p] = text
        except Exception as e:   # noqa: BLE001
            errs.append((p, e))

    threads = [threading.Thread(target=worker, args=(p,)) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs
    assert got == expected


def test_max_tokens_respected(engine):
    text, stats = run(engine, "count limit", max_tokens=3)
    assert stats.completion_tokens <= 3
    # Round-trip bound, replacement-aware. The naive
    # `len(TOK.encode(text)) <= 3` failed at the seed: cutting greedy
    # output at max_tokens can split a multi-byte UTF-8 sequence, the
    # final flush decodes the dangling bytes to U+FFFD
    # (errors='replace'), and U+FFFD re-encodes to THREE bytes — so the
    # re-encoded text can legitimately exceed max_tokens byte-tokens.
    # Each replacement char stands for at least one original byte, so
    # counting it as 1 restores the intended invariant.
    assert len(TOK.encode(text)) - 2 * text.count("�") <= 3


def test_stop_string_truncates(engine):
    full, _ = run(engine, "stop test", max_tokens=12)
    if len(full) < 2:
        pytest.skip("model emitted too little text to split a stop string")
    stop = full[1]
    text, _ = run(engine, "stop test", max_tokens=12, stop=(stop,))
    assert stop not in text
    assert text == full.split(stop, 1)[0]


def test_cancellation_frees_slot_and_others_complete(engine):
    """Closing a streaming iterator mid-request must not wedge the loop."""
    req = GenerateRequest(prompt="cancel me",
                          options=GenerateOptions(max_tokens=50))
    it = engine.generate_stream(req, RequestStats())
    next(it)          # start it, take one delta
    it.close()        # client disconnects
    # Engine still serves fresh requests correctly afterwards.
    text, _ = run(engine, "after cancel", max_tokens=8)
    assert text == want(engine, "after cancel", 8)


@pytest.mark.slow   # ~30 s/mode (decode to context-full); ci.sh full
def test_num_predict_unlimited(engine):
    """Ollama num_predict=-1 means until-EOS/context, not one token."""
    limited, _ = run(engine, "unbounded", max_tokens=2)
    unlimited, stats = run(engine, "unbounded", max_tokens=-1)
    assert unlimited.startswith(limited)
    budget = 128 - 1 - len(TOK.encode("unbounded", add_bos=True))
    assert unlimited == want(engine, "unbounded", budget)


def test_stop_string_straddling_tokens_never_leaks_prefix(engine):
    """A stop string split across token boundaries must be held back, not
    streamed then retracted (byte tokenizer = 1 char per token, so any
    multi-char stop straddles)."""
    full, _ = run(engine, "straddle", max_tokens=12)
    if len(full) < 4:
        pytest.skip("model emitted too little text")
    stop = full[2:4]                       # 2-char stop inside the output
    deltas = []
    req = GenerateRequest(prompt="straddle", options=GenerateOptions(
        max_tokens=12, stop=(stop,)))
    for d in engine.generate_stream(req, RequestStats()):
        deltas.append(d)
    text = "".join(deltas)
    assert stop not in text
    assert text == full.split(stop, 1)[0]
    # No individual delta may carry text past the stop point either.
    acc = ""
    for d in deltas:
        acc += d
        assert not acc.endswith(stop)


def test_stop_unblocks_inflight_consumers():
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128)
    req = GenerateRequest(prompt="shutdown race",
                          options=GenerateOptions(max_tokens=10_000))
    it = eng.generate_stream(req, RequestStats())
    next(it)               # request is admitted and streaming
    done = threading.Event()

    def drain():
        for _ in it:
            pass
        done.set()

    t = threading.Thread(target=drain)
    t.start()
    eng.stop()
    assert done.wait(timeout=10), "consumer wedged after scheduler stop()"
    t.join(timeout=5)


def test_recovers_after_cache_buffer_loss():
    """A failed donated call consumes the KV cache buffer; the admission
    that finds it dead fails its own request, the scheduler rebuilds the
    device state, and the engine keeps serving."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128)
    try:
        text, _ = run(eng, "before failure", max_tokens=6)
        assert text == oracle("before failure", 6)
        # Simulate a call that raised after consuming its donated input.
        eng.scheduler._cache.k.delete()
        with pytest.raises(RuntimeError, match="admission failed"):
            run(eng, "lost with the buffer", max_tokens=6)
        text, _ = run(eng, "after failure", max_tokens=6)
        assert text == oracle("after failure", 6)
    finally:
        eng.stop()


def test_sampling_with_seed_is_reproducible(engine):
    a, _ = run(engine, "seeded", max_tokens=8, temperature=0.8, seed=42)
    b, _ = run(engine, "seeded", max_tokens=8, temperature=0.8, seed=42)
    assert a == b


def test_only_a_dispatch_that_carries_a_sampling_row_counts_as_a_sort(engine):
    """``serve_decode_sort_dispatches_total`` is the sampler's own test
    made on the host: it stands still under greedy requests, moves with
    a request at temperature 0.8 (at most once a decode dispatch), and
    stands still again once that request's slot is released, although
    the slot keeps 0.8 on the device until an admission writes over it;
    the greedy request behind it still reads the oracle's tokens."""
    sort, ticks = "serve_decode_sort_dispatches_total", \
        "serve_decode_ticks_total"
    m0 = engine.metrics_snapshot()
    run(engine, "greedy first", max_tokens=8)
    m1 = engine.metrics_snapshot()
    assert m1[sort] == m0[sort] and m1[ticks] > m0[ticks]
    run(engine, "sampled", max_tokens=8, temperature=0.8, seed=7)
    m2 = engine.metrics_snapshot()
    assert 0 < m2[sort] - m1[sort] <= m2[ticks] - m1[ticks]
    text, _ = run(engine, "greedy again", max_tokens=8)
    m3 = engine.metrics_snapshot()
    assert m3[sort] == m2[sort] and m3[ticks] > m2[ticks]
    assert text == want(engine, "greedy again", 8)


def test_paged_pool_exhaustion_backpressures_then_completes():
    """A pool too small for all concurrent requests must queue the
    overflow (FIFO page backpressure), admit it as pages free, and still
    produce oracle-exact outputs for every request."""
    # 7 usable pages x 16 slots: each request needs ~2 pages, so only ~3
    # of 6 requests hold pages at once.
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=3, max_seq=128,
                    page_size=16, num_pages=8)
    try:
        prompts = [f"backpressure {i}" for i in range(6)]
        want = {p: oracle(p, 8) for p in prompts}
        got, errs = {}, []

        def worker(p):
            try:
                stats = RequestStats()
                req = GenerateRequest(prompt=p, options=GenerateOptions(
                    max_tokens=8))
                got[p] = "".join(eng.generate_stream(req, stats))
            except Exception as e:   # noqa: BLE001
                errs.append((p, e))

        threads = [threading.Thread(target=worker, args=(p,)) for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errs
        assert got == want
        # All pages returned to the pool after completion. The consumer is
        # unblocked (finish()) *before* the scheduler thread runs _release,
        # so poll: the release itself includes a device dispatch.
        deadline = time.monotonic() + 30
        while (eng.scheduler._alloc.free_pages != 7
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert eng.scheduler._alloc.free_pages == 7
    finally:
        eng.stop()


def test_paged_oversized_fails_fast_even_behind_waiters():
    """Regression: a never-fits request arriving while other requests are
    page-starved must still fail fast — not queue behind them as a
    permanent head-of-line blocker that deadlocks all future admissions."""
    # 3 usable pages x 16: the holder's budget (21 prompt + 26 + 1 = 48
    # tokens = 3 pages) pins the whole pool while it decodes.
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=3, max_seq=128,
                    page_size=16, num_pages=4)
    try:
        results, errors = {}, {}

        def worker(name, prompt, max_tokens):
            req = GenerateRequest(prompt=prompt,
                                  options=GenerateOptions(max_tokens=max_tokens))
            try:
                results[name] = "".join(eng.generate_stream(req, RequestStats()))
            except RuntimeError as e:
                errors[name] = str(e)

        hold = threading.Thread(target=worker,
                                args=("hold", "hold the pool please", 26))
        hold.start()
        deadline = time.monotonic() + 30
        while eng.scheduler._alloc.free_pages > 0 and time.monotonic() < deadline:
            time.sleep(0.005)

        small = threading.Thread(target=worker, args=("small", "ok", 4))
        small.start()
        while not eng.scheduler._waiting and time.monotonic() < deadline:
            time.sleep(0.005)

        # Needs 128 tokens = 8 pages > 3 usable: must fail fast even though
        # _waiting is (very likely) non-empty right now.
        big = threading.Thread(target=worker, args=("big", "x" * 70, 60))
        big.start()
        big.join(timeout=60)
        assert not big.is_alive(), "oversized request deadlocked behind waiters"
        assert "big" not in results and "pages" in errors["big"]

        hold.join(timeout=120)
        small.join(timeout=120)
        assert results["hold"] == oracle("hold the pool please", 26)
        assert results["small"] == oracle("ok", 4)
    finally:
        eng.stop()


def test_paged_oversized_request_fails_fast_not_deadlocks():
    """A request whose budget exceeds the whole pool must fail cleanly
    (surfaced error), not wait forever."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128,
                    page_size=16, num_pages=3)
    try:
        # prompt+generation budget needs > 2 pages (32 tokens)
        req = GenerateRequest(prompt="x" * 80,
                              options=GenerateOptions(max_tokens=60))
        with pytest.raises(RuntimeError, match="pages"):
            list(eng.generate_stream(req, RequestStats()))
        # Engine still serves a small request afterwards.
        text, _ = run(eng, "ok", max_tokens=4)
        assert text == oracle("ok", 4)
    finally:
        eng.stop()


def test_queue_timeout_fails_overdue_request():
    """A request that outlives the admission deadline fails with a
    surfaced error (SURVEY.md §5 failure-detection: serve-side request
    timeout), and the engine keeps serving afterwards. Deterministic via a
    back-dated arrival_time — the same _expired check also reaps
    page-starved waiters each scheduling round."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128,
                    queue_timeout_s=5.0)
    try:
        req = GenerateRequest(prompt="too late", arrival_time=time.monotonic() - 10,
                              options=GenerateOptions(max_tokens=4))
        with pytest.raises(RuntimeError, match="not admitted"):
            list(eng.generate_stream(req, RequestStats()))
        text, _ = run(eng, "ok", max_tokens=4)
        assert text == oracle("ok", 4)
    finally:
        eng.stop()


def test_queue_timeout_guards_capacity_not_boot():
    """The admission deadline must not fire while warmup is still
    compiling (an 8B boot is minutes of compiles): a request that
    arrives mid-warmup starts its deadline clock at warmup COMPLETION,
    and while warmup is in progress nothing expires at all."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128,
                    queue_timeout_s=5.0)
    try:
        import queue as queue_mod

        sched = eng.scheduler
        from p2p_llm_chat_tpu.serve.scheduler import _Slot

        overdue = GenerateRequest(
            prompt="x", arrival_time=time.monotonic() - 100,
            options=GenerateOptions(max_tokens=1))
        slot = _Slot(overdue, RequestStats(), queue_mod.Queue(), seed=0)
        # Warmup in progress: never expired.
        sched._warmup_done_at = None
        assert not sched._expired(slot)
        # Warmup JUST finished: the clock starts now, not at arrival.
        sched._warmup_done_at = time.monotonic()
        assert not sched._expired(slot)
        # Warmup finished long ago: the capacity deadline applies again.
        sched._warmup_done_at = time.monotonic() - 50
        assert sched._expired(slot)
    finally:
        eng.stop()


MOE_CONFIGS = ["tiny-moe", "tiny-olmoe"]


@pytest.mark.parametrize("moe_config", MOE_CONFIGS)
def test_moe_family_serves_through_same_scheduler(moe_config):
    """A routed model through the continuous-batching loop must match a
    solo mixtral prefill+decode oracle — the scheduler dispatches the
    model family from the config (models.family_for), not a hardcoded
    llama. tiny-olmoe (QK-norm, unrenormalised top-4 of 8) goes through
    the same module."""
    from p2p_llm_chat_tpu.models import mixtral

    mcfg = get_config(moe_config)
    mparams = mixtral.init_params(mcfg, jax.random.PRNGKey(1),
                                  dtype=jnp.float32)
    solo = Solo(mixtral, mcfg, TOK)

    eng = TPUEngine(mparams, mcfg, TOK, num_slots=2, max_seq=128)
    try:
        prompts = ["moe hello", "a different moe prompt"]
        want = {p: solo(mparams, p, 8) for p in prompts}
        got, errs = {}, []

        def worker(p):
            try:
                got[p] = run(eng, p, max_tokens=8)[0]
            except Exception as e:   # noqa: BLE001
                errs.append((p, e))

        threads = [threading.Thread(target=worker, args=(p,)) for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs
        assert got == want
    finally:
        eng.stop()


def test_moe_full_stack_composition_matches_oracle():
    """Round-4 verdict #3 'done' bar: MoE × paged KV × int8 KV × int8
    weights (streamed fused init) × prefix cache × speculation through
    the engine must be oracle-exact. Every feature in the stack is
    exactness-preserving under greedy decoding, so the composed output
    must equal a solo dense-cache loop on the SAME quantized tree."""
    from p2p_llm_chat_tpu.models import mixtral

    mcfg = get_config("tiny-moe")
    qparams = mixtral.init_params_quantized(mcfg, jax.random.PRNGKey(9))
    solo = Solo(mixtral, mcfg, TOK, dtype=jnp.bfloat16)

    eng = TPUEngine(qparams, mcfg, TOK, num_slots=3, max_seq=128,
                    page_size=16, kv_quant=True,
                    spec_k=2, prefix_cache=True,
                    prefix_texts=("moe prefix ",))
    try:
        prompts = ["moe prefix alpha", "moe prefix bravo",
                   "unrelated charlie"]
        want = {p: solo(qparams, p, 8) for p in prompts}
        got, errs = {}, []

        def worker(p):
            try:
                got[p] = run(eng, p, max_tokens=8)[0]
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
        # Speculation was live in the composed stack (spec_k=2 publishes
        # its acceptance counters).
        assert "serve_spec_accepted_total" in eng.metrics_snapshot()
    finally:
        eng.stop()


def test_long_prompt_truncated_to_context():
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=64)
    try:
        text, stats = run(eng, "x" * 500, max_tokens=8)
        assert stats.prompt_tokens <= 62     # max_seq - 2
        assert stats.completion_tokens <= 8
    finally:
        eng.stop()


def test_serving_bucket_rounds_up_to_warmed():
    """Post-warmup, short prompts must admit through an already-compiled
    bucket (compiling a fresh small-bucket program mid-serving would
    stall every stream); longer-than-warmed prompts keep their own."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=256)
    try:
        sched = eng.scheduler
        assert sched._serving_bucket(20) == 32          # pre-warmup: natural
        sched.warmup(prompt_buckets=(64, 128), windows=(128,))
        assert sched._serving_bucket(20) == 64          # rounded up
        assert sched._serving_bucket(100) == 128
        assert sched._serving_bucket(200) == 256        # beyond warmed: lazy
    finally:
        eng.stop()


def test_num_ctx_caps_request_context():
    """Ollama num_ctx: a request-level context cap below the server max
    truncates the prompt tail-first and bounds generation."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128)
    try:
        long_prompt = "x" * 100
        req = GenerateRequest(
            prompt=long_prompt,
            options=GenerateOptions(max_tokens=64, num_ctx=32))
        stats = RequestStats()
        text = "".join(eng.generate_stream(req, stats))
        # Prompt truncated to num_ctx-2 and completion bounded by the cap.
        assert stats.prompt_tokens <= 30
        assert stats.prompt_tokens + stats.completion_tokens <= 32
        assert isinstance(text, str)
    finally:
        eng.stop()


def test_collect_pending_respects_row_limit():
    """Regression: _collect_pending's row limit was shadowed by the
    context-budget variable, so a burst larger than the free rows
    over-collected and crashed admission (free.pop from empty) — killing
    the scheduler thread. The limit must bound the returned batch."""
    import queue as _queue

    from p2p_llm_chat_tpu.serve.scheduler import _Slot

    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128)
    try:
        sched = eng.scheduler
        # Occupy every row with live streams: with no free rows the loop's
        # _admit_pending returns before touching the queue, so the direct
        # _collect_pending calls below cannot race the scheduler thread.
        holders = []
        for name in ("hold a", "hold b"):
            it = eng.generate_stream(
                GenerateRequest(prompt=name,
                                options=GenerateOptions(max_tokens=100)),
                RequestStats())
            next(it)                      # admitted and streaming
            holders.append(it)
        slots = []
        for i in range(5):
            s = _Slot(req=GenerateRequest(prompt=f"burst {i}",
                                          options=GenerateOptions(max_tokens=4)),
                      stats=None, out_q=_queue.Queue(), seed=i)
            slots.append(s)
            sched._admit_q.put(s)
        got = sched._collect_pending(2, block=False)
        assert len(got) == 2
        got2 = sched._collect_pending(3, block=False)
        assert len(got2) == 3
        for s in slots:                   # never admitted for real
            s.cancelled.set()
        for it in holders:
            it.close()
    finally:
        eng.stop()


def test_context_round_trip_continues_conversation():
    """Ollama /api/generate context semantics through the real engine:
    generating with a returned context must reproduce the single-shot
    oracle over the concatenated token stream."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128)
    try:
        s1 = RequestStats()
        r1 = GenerateRequest(prompt="one two", options=GenerateOptions(
            max_tokens=4))
        t1 = "".join(eng.generate_stream(r1, s1))
        ctx = s1.context
        ids1 = TOK.encode("one two", add_bos=True)
        assert ctx[: len(ids1)] == ids1
        assert len(ctx) == len(ids1) + s1.completion_tokens

        s2 = RequestStats()
        r2 = GenerateRequest(prompt=" three", context=tuple(ctx),
                             options=GenerateOptions(max_tokens=4))
        t2 = "".join(eng.generate_stream(r2, s2))

        # Oracle: one dense run over the full id stream.
        full_ids = ctx + TOK.encode(" three")
        out = SOLO[False].tokens(PARAMS, full_ids, 4)
        assert t2 == TOK.decode(out)
        assert s2.context[: len(full_ids)] == full_ids
    finally:
        eng.stop()


def test_out_of_vocab_context_fails_cleanly():
    """Hostile context ids (past the vocab) must fail only the offending
    request; a co-batched innocent one still matches the oracle."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128)
    try:
        bad = GenerateRequest(prompt="x", context=(CFG.vocab_size + 7,),
                              options=GenerateOptions(max_tokens=4))
        results = {}

        def bad_worker():
            try:
                results["bad"] = "".join(
                    eng.generate_stream(bad, RequestStats()))
            except RuntimeError as e:
                results["bad_err"] = str(e)

        def good_worker():
            results["good"] = run(eng, "innocent", max_tokens=6)[0]

        ts = [threading.Thread(target=bad_worker),
              threading.Thread(target=good_worker)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert "vocabulary" in results.get("bad_err", "")
        assert results["good"] == oracle("innocent", 6)
    finally:
        eng.stop()

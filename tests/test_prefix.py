"""Shared-prefix KV cache tests (serve/prefix.py + scheduler admission).

Correctness oracle: a prompt admitted through a cached prefix (suffix-only
continuation prefill attending over KV computed once) must produce exactly
the tokens the uncached solo prefill+decode loop produces — the prefix
cache is a pure compute-reuse optimization, invisible in outputs.

The workload this exists for is the reference co-pilot: every suggestion
request starts with the same fixed template (web/streamlit_app.py:93).
"""

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
from p2p_llm_chat_tpu.serve.engine import SUGGEST_PREFIX, TPUEngine
from p2p_llm_chat_tpu.serve.prefix import PrefixEntry, PrefixStore
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
STOP_IDS = set(CFG.eos_token_ids) | {TOK.eos_id}


def oracle(prompt: str, max_new: int, max_seq: int = 256) -> str:
    """Solo batch=1 greedy loop — no prefix cache anywhere."""
    ids = TOK.encode(prompt, add_bos=True)
    cache = KVCache.create(CFG, 1, max_seq, jnp.float32)
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


def run(engine, prompt, max_tokens=10, **opts):
    stats = RequestStats()
    req = GenerateRequest(prompt=prompt, options=GenerateOptions(
        max_tokens=max_tokens, **opts))
    return "".join(engine.generate_stream(req, stats)), stats


# -- host-side store policy ---------------------------------------------------

def _entry(ids):
    return PrefixEntry(ids=tuple(ids), k=None, v=None)


def test_store_accepts_exact_length_entries():
    """Registered templates are cached at exact (non-ladder) lengths;
    match picks them up like any other entry."""
    st = PrefixStore()
    st.put(_entry(range(18)))                    # e.g. BPE-short template
    got = st.match(list(range(30)))
    assert got is not None and got.length == 18


def test_short_registered_template_engages():
    """A template below the smallest promotion grain must still cache and
    serve admissions (the real-BPE co-pilot template is ~18 tokens vs the
    64-token ladder floor); a sub-minimum one warns and no-ops."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=256,
                    prefix_texts=("short head: ",))   # 13 ids with BOS
    try:
        eng.warmup(buckets=(64,))
        store = eng.scheduler._prefix
        assert store.lengths() == [
            len(TOK.encode("short head: ", add_bos=True)) - 1]
        prompt = "short head: see you at ten?"
        text, _ = run(eng, prompt, max_tokens=8)
        assert text == oracle(prompt, 8)
        assert eng.scheduler.metrics_snapshot()[
            "serve_prefix_admits_total"] == 1
        # Sub-minimum template: warns (see scheduler log), caches nothing.
        assert eng.scheduler.register_prefix("hi") == 0
        assert len(store) == 1
    finally:
        eng.stop()


def test_store_match_returns_longest_proper_prefix():
    st = PrefixStore()
    st.put(_entry(range(64)))
    st.put(_entry(range(128)))
    ids = list(range(200))
    got = st.match(ids)
    assert got is not None and got.length == 128
    # Prompt == the 128 entry: it can't match itself (no suffix token
    # left), but the shorter entry still can.
    got = st.match(list(range(128)))
    assert got is not None and got.length == 64
    # No entry leaves a suffix: no match.
    assert st.match(list(range(64))) is None
    # Diverging head: no match.
    assert st.match([999] + list(range(199))) is None
    assert st.hits == 2


def test_store_observe_promotes_after_threshold():
    st = PrefixStore(promote_after=2)
    ids = list(range(100))
    assert st.observe(ids) is None               # first sighting
    head = st.observe(ids)                       # second: promote
    assert head == tuple(range(64))              # longest qualifying grain
    st.put(_entry(head))
    # Cached heads are not re-proposed.
    assert st.observe(ids) is None
    assert st.observe(ids) is None


def test_store_lru_eviction_bounds_entries():
    st = PrefixStore(max_entries=2)
    a, b, c = (_entry([i] * 64) for i in (1, 2, 3))
    st.put(a)
    st.put(b)
    st.match([1] * 64 + [0])                     # refresh a
    st.put(c)                                    # evicts b (LRU)
    assert len(st) == 2
    assert st.match([2] * 64 + [0]) is None
    assert st.match([3] * 64 + [0]) is c
    assert st.evictions_total == 1


def test_store_counters_and_byte_budget_cost_eviction():
    """Round-11 policy eviction: with max_bytes set, cost = bytes x
    recency picks victims (one giant stale entry goes before small warm
    ones), and the hit/miss/eviction counters export the store's
    efficacy."""
    st = PrefixStore(max_entries=10, max_bytes=100)
    big = PrefixEntry(ids=tuple(range(64)),
                      k=np.zeros(40, np.int8), v=np.zeros(40, np.int8))
    st.put(big)
    big.last_used -= 1000.0                       # long idle
    small = PrefixEntry(ids=tuple(range(100, 132)),
                        k=np.zeros(10, np.int8), v=np.zeros(10, np.int8))
    st.put(small)                                 # 100 bytes total: fits
    assert len(st) == 2 and st.evictions_total == 0
    assert st.match(list(range(64)) + [7]) is big
    assert st.hits_total == 1
    assert st.match([999] * 70) is None
    assert st.misses_total == 1
    st.put(PrefixEntry(ids=tuple(range(200, 232)),
                       k=np.zeros(10, np.int8), v=np.zeros(10, np.int8)))
    # 120 bytes > 100: the big stale entry is the cost victim — NOT the
    # small LRU-oldest-insert.
    assert st.evictions_total == 1
    assert st.match(list(range(64)) + [7]) is None
    assert st.nbytes == 40


def test_store_export_import_roundtrip_by_token_hash():
    """The cross-replica shared tier: export on the promoting store,
    import on a peer — ids, KV bits (f32 wire is lossless for f32/bf16
    entries), and match behavior all survive; junk is rejected."""
    from p2p_llm_chat_tpu.serve.prefix import token_hash
    ids = tuple(int(t) for t in np.arange(24) % 7)
    rng = np.random.RandomState(1)
    k = jnp.asarray(rng.randn(CFG.num_layers, 24, CFG.num_kv_heads,
                              CFG.head_dim), jnp.float32)
    v = jnp.asarray(rng.randn(CFG.num_layers, 24, CFG.num_kv_heads,
                              CFG.head_dim), jnp.float32)
    src = PrefixStore()
    src.put(PrefixEntry(ids=ids, k=k, v=v))
    h = token_hash(ids)
    assert h in src.hashes()
    assert src.hashes()[h]["len"] == 24
    data = src.export_payload(h)
    assert data and src.export_payload("beef") is None

    dst = PrefixStore()
    entry = dst.import_payload(data)
    assert entry is not None and entry.ids == ids
    np.testing.assert_array_equal(np.asarray(entry.k), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(entry.v), np.asarray(v))
    got = dst.match(list(ids) + [3])
    assert got is entry
    assert dst.import_payload(b"not an npz") is None
    assert dst.import_payload(data[:40]) is None


# -- admission parity against the uncached oracle -----------------------------

def test_registered_template_admission_matches_oracle():
    """Concurrent template-prefixed requests through a warmed prefix cache
    must be oracle-exact, and must actually take the prefix path."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=3, max_seq=256,
                    page_size=16, prefix_texts=(SUGGEST_PREFIX,))
    try:
        eng.warmup(buckets=(64, 128))
        store = eng.scheduler._prefix
        assert store is not None and len(store) == 1
        P = store.lengths()[0]
        # Registered templates cache at exact length minus one (not
        # ladder-snapped; the last token is left for verbatim-prompt
        # matches): byte tokenizer encodes the 89-char template + BOS
        # to 90 ids -> 89 cached.
        assert P == len(TOK.encode(SUGGEST_PREFIX, add_bos=True)) - 1

        prompts = [SUGGEST_PREFIX + f"message {i}: see you at ten?\n\nReply:"
                   for i in range(5)]
        want = {p: oracle(p, 10) for p in prompts}
        got, errs = {}, []

        def worker(p):
            try:
                got[p] = run(eng, p)[0]
            except Exception as e:   # noqa: BLE001
                errs.append((p, e))

        threads = [threading.Thread(target=worker, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs
        assert got == want
        m = eng.scheduler.metrics_snapshot()
        assert m["serve_prefix_admits_total"] == len(prompts)
        assert m["serve_prefix_tokens_saved_total"] == P * len(prompts)
    finally:
        eng.stop()


def test_auto_promotion_then_prefix_admission():
    """An unregistered head seen promote_after times is promoted; later
    prompts with the same head admit through it, oracle-exact."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=256,
                    prefix_texts=())
    try:
        import time

        head = "z y x w v u t s r q " * 5        # 100 chars -> grain 64
        prompts = [head + tail for tail in ("alpha", "beta", "gamma")]
        store = eng.scheduler._prefix
        for i, p in enumerate(prompts):           # sequential, so counts land
            text, _ = run(eng, p, max_tokens=8)
            assert text == oracle(p, 8)
            if i == 1:
                # Promotion builds are deferred to an idle scheduler tick;
                # give the loop a moment to run it before the next request.
                deadline = time.monotonic() + 10
                while len(store) < 1 and time.monotonic() < deadline:
                    time.sleep(0.02)
        assert len(store) == 1                    # promoted on 2nd sighting
        m = eng.scheduler.metrics_snapshot()
        assert m["serve_prefix_admits_total"] >= 1   # 3rd went through it
    finally:
        eng.stop()


def test_prefix_skipped_when_budget_would_overflow():
    """A near-max_seq prompt whose (prefix + suffix bucket) would overrun
    the cache must take the plain path — correct output, no prefix admit."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=160,
                    prefix_texts=("q" * 100,))
    try:
        eng.warmup(buckets=(64, 128))
        assert len(eng.scheduler._prefix) == 1
        # Registered prefix caches 100 ids (101 - 1). 141-id prompt ->
        # 41-token suffix -> 64 bucket; 100 + 64 = 164 > 160 max_seq ->
        # plain path.
        prompt = "q" * 100 + "r" * 40
        text, _ = run(eng, prompt, max_tokens=6)
        assert text == oracle(prompt, 6)
        m = eng.scheduler.metrics_snapshot()
        assert m["serve_prefix_admits_total"] == 0
    finally:
        eng.stop()


def test_prefix_composes_with_speculative_decoding():
    """Prefix admission + spec decode together stay oracle-exact (the
    prefix only changes how admission computed the KV; verify ticks read
    the same cache either way)."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=256,
                    spec_k=3, prefix_texts=(SUGGEST_PREFIX,))
    try:
        eng.warmup(buckets=(64, 128))
        prompts = [SUGGEST_PREFIX + "lunch tomorrow? lunch tomorrow?",
                   SUGGEST_PREFIX + "did you get the docs I sent?"]
        want = {p: oracle(p, 12) for p in prompts}
        got, errs = {}, []

        def worker(p):
            try:
                got[p] = run(eng, p, max_tokens=12)[0]
            except Exception as e:   # noqa: BLE001
                errs.append((p, e))

        threads = [threading.Thread(target=worker, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs
        assert got == want
        assert eng.scheduler.metrics_snapshot()[
            "serve_prefix_admits_total"] == len(prompts)
    finally:
        eng.stop()


@pytest.mark.parametrize("spec_k", [0, 2])
def test_midtraffic_warmup_does_not_perturb_live_seeded_stream(spec_k):
    """warmup() while a seeded request is mid-decode: programs run on
    the LIVE device state, so the stream's tokens must be identical to a
    run without the concurrent warmup (keys restored, lengths untouched,
    free-row-only table zeroing). spec_k>0 covers the spec warm program,
    which must round-trip the live rows' pending next tokens."""
    def serve_once(do_warmup: bool) -> str:
        eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=256,
                        page_size=16, prefix_texts=(),
                        spec_k=spec_k)
        try:
            req = GenerateRequest(prompt="steady stream", options=
                                  GenerateOptions(max_tokens=40,
                                                  temperature=0.9,
                                                  seed=1234))
            out: list[str] = []
            it = eng.generate_stream(req, RequestStats())
            out.append(next(it))          # admitted and decoding
            if do_warmup:
                done = threading.Event()

                def warm():
                    eng.scheduler.warmup(prompt_buckets=(32, 64),
                                         windows=(128, 256))
                    done.set()

                t = threading.Thread(target=warm)
                t.start()
            for delta in it:
                out.append(delta)
            if do_warmup:
                assert done.wait(timeout=120), "warmup wedged"
                t.join(timeout=10)
            return "".join(out)
        finally:
            eng.stop()

    assert serve_once(True) == serve_once(False)


def test_promotion_aot_compiles_admission_off_scheduler_thread():
    """Round 18: an auto-promoted prefix must admit through a program
    the promotion WORKER compiled ahead of time — the splice jit's call
    cache must not grow when the first post-promotion prefix-hit
    admission dispatches at a suffix bucket the warmup grain pre-warm
    did not cover (the pre-warm only runs the SMALLEST bucket; a lazy
    compile here lands the whole multi-second XLA compile inside
    decode_stall_ms for every in-flight stream)."""
    import time

    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=256,
                    prefix_texts=())
    try:
        eng.warmup(buckets=(64, 128))
        sched = eng.scheduler
        store = sched._prefix
        head = "z y x w v u t s r q " * 5          # 100 chars -> grain 64
        # Two short-tail sightings promote the 64-id head.
        for tail in ("alpha", "beta"):
            p = head + tail
            text, _ = run(eng, p, max_tokens=8)
            assert text == oracle(p, 8)
        deadline = time.monotonic() + 30
        while len(store) < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(store) == 1, "head never promoted"
        # The worker's AOT programs merged with the install — including
        # the 128 suffix bucket no pre-warm covers (single-shot at the
        # default prefill_chunk=256: 128 is not chunkable).
        assert any(k[0] == 64 and k[1] == 128
                   for k in sched._admit_prefix_aot), \
            sorted(sched._admit_prefix_aot)
        n_before = sched._admit_prefix_j._cache_size()
        chunk_keys = set(sched._prefill_chunk_programs)
        # Third prompt: same head, 60-char tail -> 97-token suffix ->
        # the 128 bucket. Must admit through the cached prefix WITHOUT
        # growing any scheduler-thread compile cache.
        p = head + "the quick brown fox jumps over the lazy dog again and more"
        text, _ = run(eng, p, max_tokens=8)
        assert text == oracle(p, 8)
        m = sched.metrics_snapshot()
        assert m["serve_prefix_admits_total"] >= 1
        assert sched._admit_prefix_j._cache_size() == n_before, \
            "prefix-hit admission compiled on the scheduler thread"
        assert set(sched._prefill_chunk_programs) == chunk_keys
    finally:
        eng.stop()

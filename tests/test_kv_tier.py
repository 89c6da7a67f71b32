"""Multi-tier KV tests: host-RAM session parking + wake (serve/kv_tier.py).

Correctness contract: park/wake round-trips the RAW pool words (int8 +
scales included), so a session resumed after parking produces BYTE-
identical greedy output to the same session resumed while still
resident — tiering is a capacity/latency optimization, invisible in
outputs. The A/B legs here run the same two-turn conversation through
two engines that differ only in whether the session was forced to host
RAM between turns.

Fast legs (tier-1, wired explicitly into ci.sh fast) cover the policy
unit tests, the ops-level raw-bits round-trip, and the paged-int8 A/B;
the bf16 / prefix-composition matrix and the eviction-pressure
leg are slow-marked into ci.sh full (the tier-1 sweep brushes its 870 s
container budget — ROADMAP note).
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops.paged_kv import (PageAllocator, PagedKVCache,
                                           gather_pages, scatter_pages,
                                           write_prefill_row)
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                            GenerateRequest, RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.serve.kv_tier import (KVTier, SessionKV, cost_evict)
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)

PROMPT1 = "hello there, how are you doing today my good friend?"
PROMPT2 = " tell me one more thing before we finish?"


def run(engine, prompt, session="", max_tokens=8, ctx=()):
    stats = RequestStats()
    req = GenerateRequest(prompt=prompt, session=session,
                          context=tuple(ctx),
                          options=GenerateOptions(max_tokens=max_tokens,
                                                  temperature=0.0, seed=1))
    return "".join(engine.generate_stream(req, stats)), stats


def make_engine(kv_quant=True, prefix=False, pages=None,
                host_gb=1.0, idle_s=1e9, slots=2):
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=slots, max_seq=256,
                    page_size=64, num_pages=pages,
                    prefix_cache=prefix, kv_quant=kv_quant,
                    kv_host_gb=host_gb, kv_idle_s=idle_s)
    eng.warmup(buckets=(64, 128))
    return eng


def wait_for(fn, timeout=5.0, msg="condition"):
    """Session retention runs on the scheduler thread moments AFTER the
    consumer sees its final delta — poll instead of asserting raw."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def force_park(sched, want=1, timeout=10.0):
    """Flip the idle threshold to zero and wait for the scheduler loop's
    own sweep to park (the loop owns the device buffers — tests must
    never drive _park_session from another thread)."""
    sched._tier.idle_s = 0.0
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if sched._tier.counts()[1] >= want:
            sched._tier.idle_s = 1e9
            return
        time.sleep(0.02)
    raise AssertionError(
        f"loop never parked {want} session(s): {sched._tier.counts()}")


def two_turns(eng, session="sess", park=False):
    t1, s1 = run(eng, PROMPT1, session)
    if park:
        force_park(eng.scheduler)
        assert eng.scheduler._tier.counts() == (0, 1)
    t2, _ = run(eng, PROMPT2, session, ctx=s1.context)
    return t1, t2


# -- policy unit tests --------------------------------------------------------

def test_cost_evict_prefers_big_stale():
    now = 1000.0
    items = [("small-stale", 10, now - 100.0),
             ("big-stale", 1000, now - 100.0),
             ("big-warm", 1000, now - 0.1),
             ("small-warm", 10, now - 0.1)]
    # Free 1000 bytes: the big stale entry alone covers it.
    assert cost_evict(items, 1000, now=now) == ["big-stale"]
    # A little more: the next victim by cost is small-stale (10 bytes x
    # 100 s idle = 1000) over big-warm (1000 x 0.1 = 100).
    assert cost_evict(items, 1005, now=now) == ["big-stale", "small-stale"]
    assert cost_evict(items, 0, now=now) == []


def test_session_index_key_head_and_divergence():
    tier = KVTier(host_bytes=1 << 20)
    toks = tuple(range(40))
    tier.insert(SessionKV(key="sid:a", tokens=toks, length=40,
                          host=((np.zeros(2), np.zeros(2)), 1),
                          nbytes=32))
    # Explicit key, proper prefix extension -> hit.
    assert tier.lookup("sid:a", list(range(50))) is not None
    # Derived head lookup (no key): first 32 ids match verbatim.
    assert tier.lookup("", list(range(50))) is not None
    # Prompt == session tokens exactly: no suffix to prefill -> miss.
    assert tier.lookup("sid:a", list(range(40))) is None
    # Diverged history under the SAME key drops the stale session.
    assert tier.lookup("sid:a", list(range(39)) + [999, 7]) is None
    assert tier.counts() == (0, 0)


def test_host_budget_victims_and_claim():
    tier = KVTier(host_bytes=100)
    old = SessionKV(key="old", tokens=(1, 2), length=2,
                    host=((np.zeros(2),), 1), nbytes=80,
                    last_used=time.monotonic() - 50)
    new = SessionKV(key="new", tokens=(3, 4), length=2,
                    host=((np.zeros(2),), 1), nbytes=80)
    tier.insert(old)
    tier.insert(new)
    # stats() is the read API: bare tier.host_bytes reads off-thread
    # fail under GRAFTCHECK_LOCKCHECK=1 (the annotations have teeth).
    assert tier.stats()["host_bytes"] == 160
    victims = tier.host_victims()
    assert victims and victims[0].key == "old"   # bytes x recency
    tier.drop(victims[0])
    assert tier.stats()["host_bytes"] == 80
    assert tier.stats()["evicted_total"] == 1
    # claim removes the session; a second claim finds nothing.
    assert tier.claim("new", [3, 4, 5]) is not None
    assert tier.claim("new", [3, 4, 5]) is None


# -- ops-level raw-bits round-trip --------------------------------------------

@pytest.mark.parametrize("quantized", [True, False])
def test_gather_scatter_roundtrip_is_bit_exact(quantized):
    """park (gather) -> host -> wake (scatter into DIFFERENT physical
    pages) preserves the exact pool words — int8 payload and the
    head-major scales included."""
    cache = PagedKVCache.create(CFG, 2, 12, 4, quantized=quantized,
                                dtype=jnp.float32)
    alloc = PageAllocator(12, 4)
    pages = alloc.alloc(3)
    L, Hkv, D = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim
    rng = np.random.RandomState(0)
    k = jnp.asarray(rng.randn(L, 10, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(L, 10, Hkv, D), jnp.float32)
    table_row = pages + [0] * (cache.max_pages_per_row - len(pages))
    cache = write_prefill_row(cache, k, v, jnp.int32(0), jnp.int32(10),
                              jnp.asarray(table_row, jnp.int32))
    got = jax.jit(gather_pages)(cache, jnp.asarray(pages + [0],
                                                   jnp.int32))
    host = tuple(None if a is None else np.asarray(a) for a in got)
    # Wake into different pages of a FRESH pool.
    cache2 = PagedKVCache.create(CFG, 2, 12, 4, quantized=quantized,
                                 dtype=jnp.float32)
    alloc2 = PageAllocator(12, 4)
    alloc2.alloc(2)                      # displace: different ids
    pages2 = alloc2.alloc(3)
    dev = tuple(None if a is None else jnp.asarray(a) for a in host)
    cache2 = jax.jit(scatter_pages, donate_argnums=(0,))(
        cache2, jnp.asarray(pages2 + [0], jnp.int32), *dev)
    np.testing.assert_array_equal(np.asarray(cache2.k[:, pages2]),
                                  host[0][:, :3])
    np.testing.assert_array_equal(np.asarray(cache2.v[:, pages2]),
                                  host[1][:, :3])
    if quantized:
        np.testing.assert_array_equal(
            np.asarray(cache2.k_scale[:, pages2]), host[2][:, :3])
        np.testing.assert_array_equal(
            np.asarray(cache2.v_scale[:, pages2]), host[3][:, :3])


# -- park/wake bit-identity (the acceptance contract) -------------------------

def test_park_wake_bit_identity_paged_int8():
    """The tentpole oracle: a session parked to host RAM and woken
    resumes with greedy output BYTE-identical to the same session
    resumed while resident — across the int8 pool, scales included."""
    a = make_engine()
    try:
        a1, a2 = two_turns(a, park=False)   # resident wake
        snap = a.scheduler.metrics_snapshot()
        assert snap["kv_waked_total"] == 1
        assert snap["kv_wake_tokens_saved_total"] > 0
        assert snap["kv_wake_p50_ms"] > 0
        for k in ("kv_resident_sessions", "kv_parked_sessions",
                  "kv_open_sessions", "kv_host_bytes",
                  "kv_parked_total", "kv_wake_cold_total",
                  "kv_evicted_total", "kv_pages_freed_total",
                  "kv_wake_p95_ms"):
            assert k in snap, k
        # Derived-head wake (same engine): bare /api/generate context
        # continuation with NO session id still wakes — the token-head
        # index finds the session.
        d1, ds = run(a, "a different anonymous conversation starter!",
                     session="")
        wait_for(lambda: a.scheduler._tier.counts()[0] >= 2,
                 msg="derived-head retention")
        run(a, PROMPT2, session="", ctx=ds.context)
        assert a.scheduler.metrics_snapshot()["kv_waked_total"] == 2
    finally:
        a.stop()
    b = make_engine()
    try:
        b1, b2 = two_turns(b, park=True)    # parked + woken from host
        snap = b.scheduler.metrics_snapshot()
        assert snap["kv_parked_total"] == 1
        assert snap["kv_waked_total"] == 1
        assert snap["kv_pages_freed_total"] >= 1
    finally:
        b.stop()
    assert a1 == b1
    assert a2 == b2, "park/wake changed resumed output"


@pytest.mark.slow   # a third engine warmup; ci.sh full
def test_session_rotates_and_rewakes_across_turns():
    """Turn 3 wakes the session state turn 2 re-retained (the open
    session follows the conversation, not the request)."""
    eng = make_engine()
    try:
        t1, s1 = run(eng, PROMPT1, "s")
        t2, s2 = run(eng, PROMPT2, "s", ctx=s1.context)
        force_park(eng.scheduler)
        t3, _ = run(eng, " and a third turn now!", "s", ctx=s2.context)
        snap = eng.scheduler.metrics_snapshot()
        assert snap["kv_waked_total"] == 2
        assert snap["kv_parked_total"] == 1
        wait_for(lambda: eng.scheduler._tier.counts() == (1, 0),
                 msg="turn-3 retention")
    finally:
        eng.stop()


@pytest.mark.slow
def test_park_wake_bit_identity_paged_bf16_pool():
    """bf16 (non-quantized) pool: same A/B contract as the int8 leg."""
    a = make_engine(kv_quant=False)
    try:
        a1, a2 = two_turns(a, park=False)
    finally:
        a.stop()
    b = make_engine(kv_quant=False)
    try:
        b1, b2 = two_turns(b, park=True)
    finally:
        b.stop()
    assert (a1, a2) == (b1, b2)


@pytest.mark.slow
def test_park_wake_composes_with_prefix_cache():
    """Prefix-hit admission for turn 1 (the co-pilot template head),
    then park/wake for turn 2 — the two KV-reuse tiers compose and the
    A/B identity holds through both."""
    head = "You are a helpful assistant. Draft a concise, friendly " \
           "reply to the following message:\n\n"
    prompt = head + "are we still on for ten?\n\nReply:"

    def turns(park):
        eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=256,
                        page_size=64,
                        prefix_cache=True, prefix_texts=(head,),
                        kv_quant=True, kv_host_gb=1.0, kv_idle_s=1e9)
        try:
            eng.warmup(buckets=(64, 128))
            t1, s1 = run(eng, prompt, "p")
            snap = eng.scheduler.metrics_snapshot()
            assert snap["serve_prefix_admits_total"] == 1   # prefix hit
            assert snap["prefix_hits_total"] >= 1
            if park:
                force_park(eng.scheduler)
            t2, _ = run(eng, PROMPT2, "p", ctx=s1.context)
            assert eng.scheduler.metrics_snapshot()[
                "kv_waked_total"] == 1
            return t1, t2
        finally:
            eng.stop()

    assert turns(park=False) == turns(park=True)


@pytest.mark.slow
def test_eviction_under_pressure_falls_back_cold():
    """A sub-session host budget evicts the parked session entirely;
    the follow-up silently cold-admits with a well-formed stream and
    the conversation re-opens as a fresh session."""
    eng = make_engine(host_gb=1e-7)      # ~100 bytes: nothing fits
    try:
        t1, s1 = run(eng, PROMPT1, "e")
        # Flip the idle threshold: the sweep parks, the insert trips
        # the byte budget, _tier_enforce evicts — all on the loop.
        eng.scheduler._tier.idle_s = 0.0
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if eng.scheduler._tier.stats()["evicted_total"] >= 1:
                break
            time.sleep(0.02)
        assert eng.scheduler._tier.stats()["evicted_total"] >= 1
        assert eng.scheduler._tier.counts() == (0, 0)
        t2, _ = run(eng, PROMPT2, "e", ctx=s1.context)
        snap = eng.scheduler.metrics_snapshot()
        assert snap["kv_waked_total"] == 0          # cold re-admission
        assert snap["kv_wake_cold_total"] >= 1
        assert len(t2) > 0
    finally:
        eng.stop()


@pytest.mark.slow
def test_pool_pressure_parks_residents_for_new_admissions():
    """A pool sized for ~2 concurrent requests keeps MANY more sessions
    open: finished residents park under allocation pressure instead of
    blocking new admissions — the capacity story, in miniature."""
    # 2 slots x ~3 pages per request + 1 garbage page.
    eng = make_engine(pages=7, slots=2)
    try:
        stats = {}
        for i in range(6):
            _, s = run(eng, f"session {i}: " + PROMPT1, f"m{i}")
            stats[i] = s
        wait_for(lambda: eng.scheduler.metrics_snapshot()[
            "kv_open_sessions"] == 6, msg="all sessions open")
        snap = eng.scheduler.metrics_snapshot()
        # 6 sessions x 2 retained pages >> the 6-page pool: at least
        # half were pressure-parked to host (the rest pack the pool).
        assert snap["kv_parked_total"] >= 3     # pressure-parked
        assert snap["kv_host_bytes"] > 0
        # Every parked session still wakes correctly.
        t2, _ = run(eng, PROMPT2, "m0", ctx=stats[0].context)
        assert eng.scheduler.metrics_snapshot()["kv_waked_total"] == 1
    finally:
        eng.stop()



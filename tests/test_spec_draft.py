"""Draft-model speculative decoding tests (round 9, alongside
tests/test_spec.py).

The load-bearing properties:

- **Exactness**: greedy serving output is BIT-identical with the
  resident drafter on vs off (drafts are point-mass greedy proposals,
  so the existing spec_verify_batched acceptance math stays exact) —
  including under chunked prefill and fused-K decode.
- **Hybrid routing**: the n-gram source proposes first and the model
  drafter fills in on misses; per-source counters expose which one is
  earning its verify cost.
- **Drafter-KV rollback**: after partial acceptance the drafter's
  valid-KV prefix rewinds to the last accepted position — its next
  proposals equal a fresh drafter fed the full context.
- **Cold-start throttle**: a source that never accepts stops paying
  for speculation within a few ticks (per-source EMA seeded at 2x the
  floor, fast zero-acceptance decay).

The freeform synthetic pair (models/synth.py mode="freeform") gives a
CPU-sized target+drafter that share one pseudo-random 95-token
successor cycle: the drafter genuinely predicts the target (acceptance
~100%) while trailing n-grams essentially never repeat (prompt-lookup
scores ~0) — the free-form statistic the round exists to win.
"""

import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.synth import quote_params, successor_map
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.draft_model import ModelDrafter
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

from solo import Solo

pytestmark = pytest.mark.model

CFG = get_config("tiny")
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
# Freeform pair: target + 1-layer drafter share the successor map.
FREEFORM = quote_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32,
                        mode="freeform")
DCFG = CFG.with_(num_layers=1, name="tiny-draft")
DRAFT_FF = quote_params(DCFG, jax.random.PRNGKey(1), dtype=jnp.float32,
                        mode="freeform")
# Uncorrelated drafter (plain random init): proposals ~never accepted.
DRAFT_RAND = llama.init_params(DCFG, jax.random.PRNGKey(3),
                               dtype=jnp.float32)
# A prompt with no internal repetition: the n-gram index has nothing.
PROMPT = "Tell me something new about the harbor lights"


# The sequential greedy loop on the model layer's dense cache
# (tests/solo.py).
greedy_oracle = Solo(llama, CFG, TOK, max_seq=256)


def run_engine(params, prompt: str, max_new: int, *, draft=None,
               spec_k: int = 4, **kw) -> tuple[str, dict]:
    eng = TPUEngine(params, CFG, TOK, num_slots=2, max_seq=256,
                    spec_k=spec_k, draft=draft, **kw)
    try:
        req = GenerateRequest(prompt=prompt,
                              options=GenerateOptions(max_tokens=max_new))
        got = "".join(eng.generate_stream(req, RequestStats()))
        return got, eng.metrics_snapshot()
    finally:
        eng.stop()


def src(snap: dict, key: str, source: str) -> float:
    return snap[f'{key}{{source="{source}"}}']


# -- config + synth construction ----------------------------------------------

def test_draft_400m_registered():
    cfg = get_config("draft-400m")
    assert not cfg.tie_embeddings          # synth workloads need a head
    assert cfg.vocab_size == get_config("llama3.1-8b").vocab_size
    assert cfg.num_heads % cfg.num_kv_heads == 0
    # Vocab-cloning for different-vocab targets (bench pairing).
    assert cfg.with_(vocab_size=32768).vocab_size == 32768


def test_freeform_successor_map_is_one_long_cycle():
    succ = successor_map(CFG.vocab_size, mode="freeform")
    # Walk the cycle from a printable id: it must visit the whole
    # printable range before returning (no short repeats for n-grams).
    t, seen = 65, []
    for _ in range(95):
        t = int(succ[t])
        assert 32 <= t < 127
        seen.append(t)
    assert len(set(seen)) == 95
    # Quote mode keeps its 16-token blocks (the two statistics differ).
    q = successor_map(CFG.vocab_size, mode="quote")
    t, qseen = 65, set()
    for _ in range(64):
        t = int(q[t])
        qseen.add(t)
    assert len(qseen) == 16


# -- hybrid source selection --------------------------------------------------

def test_freeform_ngram_misses_model_drafts_and_wins():
    """On free-form output the n-gram index proposes ~nothing; the model
    drafter fills in, its drafts land, and greedy output stays
    oracle-exact. Per-source EMAs are independent: the model's rises on
    its accepted drafts while the consulted-but-silent n-gram source
    decays toward probes (a never-proposing source must stop keeping
    the spec path unpipelined) — neither throttles the other."""
    want = greedy_oracle(FREEFORM, PROMPT, 24)
    got, snap = run_engine(FREEFORM, PROMPT, 24, draft=(DRAFT_FF, DCFG))
    assert got == want
    assert src(snap, "serve_spec_proposed_total", "ngram") == 0
    assert src(snap, "serve_spec_proposed_total", "model") > 0
    assert src(snap, "serve_spec_accepted_total", "model") > 0
    # The shared successor cycle means near-perfect acceptance.
    assert src(snap, "serve_spec_accept_rate", "model") > 0.9
    floor = 0.5
    assert snap['serve_spec_accept_ema{source="model"}'] > floor
    # ngram was consulted every spec tick and proposed nothing: it
    # backs off (below its seed) without ever gating the model source.
    assert snap['serve_spec_accept_ema{source="ngram"}'] < 1.0


@pytest.mark.slow
def test_quote_workload_ngram_still_first():
    """On the quote workload the n-gram source keeps its free wins —
    model drafting must not displace it once the output repeats (n-gram
    is consulted first), and output stays oracle-exact."""
    qparams = quote_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    dq = quote_params(DCFG, jax.random.PRNGKey(1), dtype=jnp.float32)
    # Long enough that the n-gram source — throttled while the output
    # has not repeated yet — gets a probe tick after the 16-token cycle
    # establishes, accepts, and re-seeds to per-tick proposing.
    want = greedy_oracle(qparams, PROMPT, 96)
    got, snap = run_engine(qparams, PROMPT, 96, draft=(dq, DCFG))
    assert got == want
    # Output settles into the 16-token cycle: the n-gram index catches
    # it and proposes (for free) on later ticks.
    assert src(snap, "serve_spec_proposed_total", "ngram") > 0
    assert src(snap, "serve_spec_accepted_total", "ngram") > 0


# -- exactness: draft on vs off ----------------------------------------------

def test_greedy_bit_identical_draft_on_off():
    """Bit-identity with SERVE_DRAFT on vs off, on the REJECTION-heavy
    path: an uncorrelated random drafter proposes garbage every tick and
    the exact-acceptance math must discard it invisibly."""
    want = greedy_oracle(FREEFORM, PROMPT, 20)
    off, _ = run_engine(FREEFORM, PROMPT, 20, draft=None, page_size=16)
    on, snap = run_engine(FREEFORM, PROMPT, 20, draft=(DRAFT_RAND, DCFG),
                          page_size=16)
    assert off == want
    assert on == want
    assert src(snap, "serve_spec_proposed_total", "model") > 0


@pytest.mark.slow
@pytest.mark.parametrize("prefill_chunk", [0, 64])
@pytest.mark.parametrize("fuse", [1, 4])
def test_spec_draft_chunked_fused_matrix(prefill_chunk, fuse):
    """The spec x chunked-prefill x fused-K interaction table with the
    model drafter live: a long no-repeat prompt admits through the chunk
    ladder (when enabled), decode ramps fused K between spec ticks, and
    greedy output stays oracle-exact throughout."""
    prompt = ("The delivery schedule moved: vans depart at dawn, barges "
              "follow the evening tide, and couriers fill whatever gaps "
              "remain across the city")           # ~130 tokens, chunked
    want = greedy_oracle(FREEFORM, prompt, 24)
    got, snap = run_engine(FREEFORM, prompt, 24, draft=(DRAFT_FF, DCFG),
                           page_size=16, prefill_chunk=prefill_chunk,
                           decode_fuse_max=fuse)
    assert got == want
    assert src(snap, "serve_spec_accepted_total", "model") > 0


def test_spec_draft_chunked_fused_fast_leg():
    """Tier-1 leg of the interaction matrix: the full composition
    (paged KV + chunked prefill + fused K) in one engine."""
    prompt = ("The delivery schedule moved: vans depart at dawn, barges "
              "follow the evening tide, and couriers fill whatever gaps "
              "remain across the city")
    want = greedy_oracle(FREEFORM, prompt, 24)
    got, snap = run_engine(FREEFORM, prompt, 24, draft=(DRAFT_FF, DCFG),
                           page_size=16,
                           prefill_chunk=64, decode_fuse_max=4)
    assert got == want
    assert src(snap, "serve_spec_accepted_total", "model") > 0


# -- drafter-KV rollback ------------------------------------------------------

@pytest.mark.parametrize("accepted", [0, 2, 4])
def test_drafter_kv_rollback_matches_fresh(accepted):
    """After the target accepts ``accepted`` of K drafts (+ a
    correction), the drafter's valid-KV prefix must equal reality: its
    next proposals are identical to a FRESH drafter fed the full new
    context from scratch."""
    K = 4
    ctx = TOK.encode("rollback context goes here", add_bos=True)
    d = ModelDrafter(DRAFT_FF, DCFG, num_slots=2, max_seq=256, k=K)
    # Mirror the scheduler: the prompt prefills; the first sampled token
    # joins the context unfed (pending >= 1 at every draft). Contexts
    # pass as (prompt_ids, generated_ids) pairs — the DraftSource
    # zero-copy contract.
    d.prefill([0], {0: ctx[:-1]})
    props = d.draft_batch([0], {0: (ctx[:-1], ctx[-1:])})[0]
    assert len(props) == K
    d.observe(0, accepted)
    # New context: accepted drafts + an arbitrary correction token.
    tail = ctx[-1:] + props[:accepted] + [65]
    got = d.draft_batch([0], {0: (ctx[:-1], tail)})[0]

    fresh = ModelDrafter(DRAFT_FF, DCFG, num_slots=2, max_seq=256, k=K)
    fresh.prefill([0], {0: ctx[:-1]})
    want = fresh.draft_batch([0], {0: (ctx[:-1], tail)})[0]
    assert got == want


def test_drafter_release_and_readmit_resets_row():
    """A row released and re-admitted with a different context must
    draft from the NEW context only."""
    K = 3
    d = ModelDrafter(DRAFT_FF, DCFG, num_slots=1, max_seq=256, k=K)
    a = TOK.encode("first occupant of the row", add_bos=True)
    d.prefill([0], {0: a[:-1]})
    d.draft_batch([0], {0: (a[:-1], a[-1:])})
    d.release(0)
    b = TOK.encode("second occupant, different text", add_bos=True)
    d.prefill([0], {0: b[:-1]})
    got = d.draft_batch([0], {0: (b[:-1], b[-1:])})[0]
    fresh = ModelDrafter(DRAFT_FF, DCFG, num_slots=1, max_seq=256, k=K)
    fresh.prefill([0], {0: b[:-1]})
    assert got == fresh.draft_batch([0], {0: (b[:-1], b[-1:])})[0]


# -- cold-start throttle ------------------------------------------------------

def test_ema_cold_start_throttles_within_a_few_ticks():
    """A source that never accepts must stop speculating fast: seeded at
    2x the floor with the fast zero-acceptance decay, the uncorrelated
    drafter throttles after ~3 spec ticks instead of burning a verify
    forward per emitted token (the old spec_k-optimistic seed wasted
    ~20)."""
    from p2p_llm_chat_tpu.serve import scheduler as sched_mod
    assert sched_mod._SPEC_EMA_SEED == pytest.approx(
        2 * sched_mod._SPEC_EMA_FLOOR)
    # Constants math: zero-acceptance ticks cross the floor within 3.
    ema, ticks = sched_mod._SPEC_EMA_SEED, 0
    while ema >= sched_mod._SPEC_EMA_FLOOR:
        ema *= (1 - sched_mod._SPEC_EMA_ZERO_ALPHA)
        ticks += 1
    assert ticks <= 3

    got, snap = run_engine(FREEFORM, PROMPT, 32, draft=(DRAFT_RAND, DCFG))
    assert got == greedy_oracle(FREEFORM, PROMPT, 32)
    assert snap[f'serve_spec_accept_ema{{source="model"}}'] \
        < sched_mod._SPEC_EMA_FLOOR
    # Throttled after ~3 ticks + probes: far below the one-verify-per-
    # token worst case (32 ticks x K=4 = 128 proposed).
    assert src(snap, "serve_spec_proposed_total", "model") <= 48


# (Per-source EMA independence is asserted inside
# test_freeform_ngram_misses_model_drafts_and_wins — same engine run,
# one fewer tier-1 boot.)

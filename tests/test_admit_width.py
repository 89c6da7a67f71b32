"""Admission dispatches as many rows as it collected.

- the rule (``_admit_widths`` / ``_admit_width``): two widths a bucket,
  1 and 2 where two rows stay inside a dispatch's token cap; the
  narrowest that holds the group is chosen; a fixed ``admit_chunk``
  stays the only width;
- a lone request runs the 1-row program, single-shot and through a
  chunk ladder, prefix hit and miss, and the counters at the dispatch
  site say so (``serve_admit_rows_padded_total``,
  ``serve_prefill_tokens_padded_total``);
- k requests collected together run the narrowest warmed width that
  holds them, pair by pair (tests/test_admit_pairs.py: what a pair
  generates, and that nothing waits for a partner);
- what a request generates does not depend on the width it was
  admitted at (dense and MoE streams; dense logits within float32
  rounding), and the requests come before the dummy entries, which
  would otherwise take a capacity-bounded MoE's buckets from them;
- the warm-up surface: at the benchmark's geometry no more admission
  programs than the {8, 32} ladder had, and after a warm-up no
  admission the chooser can pick compiles.

All on the CPU: counts and control flow, never a device timing.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama, mixtral
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.serve import scheduler as sched_mod
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler, _WarmupJob
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

from solo import jit_model

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
MOE_CFG = get_config("tiny-moe")

CHUNK = 32
HEAD = "template head, shared by every request in the fleet: "
SHORT = "are we on for ten?"                       # 19 tokens: bucket 32
LONG = ("Summarize the following discussion thread about quarterly "
        "planning, the picnic schedule, and the office move into "
        "one sentence:")                           # bucket 128: 4 chunks
GREEDY = GenerateOptions(max_tokens=8, temperature=0.0, seed=1)


def _scheduler(params=PARAMS, config=CFG, **kw) -> BatchScheduler:
    kw.setdefault("num_slots", 8)
    kw.setdefault("max_seq", 256)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("decode_fuse_max", 1)
    return BatchScheduler(params, config, TOK, **kw)


def _run(sched, prompt, opts=GREEDY) -> str:
    return "".join(sched.submit(GenerateRequest(prompt=prompt, options=opts),
                                RequestStats()))


def _together(sched, prompts) -> list:
    """Submit ``prompts`` so that one collection takes them all: the loop
    is held inside a queued job while they are enqueued."""
    gate, void = threading.Event(), threading.Event()
    job = _WarmupJob(lambda: gate.wait(timeout=60), void)
    sched._admit_q.put(job)
    streams = [sched.submit(GenerateRequest(prompt=p, options=GREEDY),
                            RequestStats()) for p in prompts]
    gate.set()
    assert job.done.wait(timeout=60)
    return ["".join(s) for s in streams]


def _delta(sched, before: dict, key: str):
    return sched.metrics_snapshot()[key] - before[key]


# -- the rule ----------------------------------------------------------------

@pytest.mark.parametrize("slots,footprint,widths", [
    (32, 128, (1, 2)), (32, 256, (1, 2)), (32, 512, (1, 2)),
    (32, 1024, (1, 2)), (32, 2048, (1,)),
    (32, 88 + 128, (1, 2)), (32, 88 + 256, (1, 2)), (32, 88 + 512, (1, 2)),
    (32, 88 + 1024, (1,)),
    (2, 128, (1, 2)), (5, 128, (1, 2)), (1, 128, (1,)),
])
def test_two_widths_a_bucket_the_second_capped_by_tokens(slots, footprint,
                                                         widths):
    sched = _scheduler(num_slots=slots)
    try:
        assert sched._admit_widths(footprint) == widths
    finally:
        sched.stop()


@pytest.mark.parametrize("n,footprint,R", [
    (1, 256, 1), (2, 256, 2), (8, 256, 2), (9, 256, 2), (32, 256, 2),
    (1, 512, 1), (3, 512, 2), (5, 512, 2), (2, 1024, 2), (3, 2048, 1),
])
def test_width_is_the_narrowest_that_holds_the_group(n, footprint, R):
    sched = _scheduler(num_slots=32)
    try:
        assert sched._admit_width(n, footprint) == R
    finally:
        sched.stop()


@pytest.mark.parametrize("footprint,R", [(128, 4), (4096, 4), (8192, 2)])
def test_a_fixed_admit_chunk_stays_the_only_width(footprint, R):
    sched = _scheduler(num_slots=32, admit_chunk=4)
    try:
        assert sched._admit_widths(footprint) == (R,)
        assert sched._admit_width(1, footprint) == R
        assert sched._admit_width(9, footprint) == R
    finally:
        sched.stop()


# -- a lone request ------------------------------------------------------------

@pytest.mark.parametrize("hit", [False, True], ids=["miss", "hit"])
@pytest.mark.parametrize("body,chunks", [(SHORT, 0), (LONG, 4)],
                         ids=["single-shot", "ladder"])
def test_a_lone_request_is_one_row(body, chunks, hit):
    sched = _scheduler(prefix_cache=True)
    try:
        P = sched.register_prefix(HEAD)
        assert P > 0
        prompt = (HEAD if hit else "") + body
        n = len(TOK.encode(prompt, add_bos=True)) - (P if hit else 0)
        S = sched._serving_bucket(n)
        before = sched.metrics_snapshot()
        _run(sched, prompt)
        assert _delta(sched, before, "serve_admit_batches_total") == 1
        assert _delta(sched, before, "serve_admit_rows_padded_total") == 1
        assert _delta(sched, before, "serve_admitted_total") == 1
        assert _delta(sched, before, "serve_prefill_tokens_total") == n
        assert _delta(sched, before,
                      "serve_prefill_tokens_padded_total") == 1 * S
        assert _delta(sched, before, "prefill_chunks_total") == chunks
        assert _delta(sched, before,
                      "serve_prefix_admits_total") == (1 if hit else 0)
    finally:
        sched.stop()


# -- requests collected together ---------------------------------------------------

@pytest.mark.parametrize("k,slots,dispatches", [
    (2, 8, [2]), (3, 4, [2, 1]), (2, 2, [2]), (8, 8, [2, 2, 2, 2]),
    (5, 4, [2, 2, 1]),     # four rows free: the fifth follows alone
])
def test_a_group_runs_the_narrowest_width_that_holds_it(k, slots, dispatches):
    sched = _scheduler(num_slots=slots)
    try:
        before = sched.metrics_snapshot()
        prompts = [f"{SHORT} #{i}" for i in range(k)]
        assert len(_together(sched, prompts)) == k
        S = 32
        assert _delta(sched, before, "serve_admitted_total") == k
        assert _delta(sched, before,
                      "serve_admit_batches_total") == len(dispatches)
        assert _delta(sched, before,
                      "serve_admit_rows_padded_total") == sum(dispatches)
        assert _delta(sched, before,
                      "serve_prefill_tokens_padded_total") == (
                          sum(dispatches) * S)
        assert _delta(sched, before, "serve_prefill_tokens_total") == sum(
            len(TOK.encode(p, add_bos=True)) for p in prompts)
    finally:
        sched.stop()


def test_two_long_prompts_share_one_ladder():
    sched = _scheduler(num_slots=4)
    try:
        before = sched.metrics_snapshot()
        # 100 + 3 characters and a BOS: still the 128 bucket, four chunks.
        group = [f"{LONG[:100]} #{i}" for i in range(2)]
        assert len(_together(sched, group)) == 2
        assert _delta(sched, before, "serve_admit_batches_total") == 1
        assert _delta(sched, before, "serve_admit_rows_padded_total") == 2
        assert _delta(sched, before, "prefill_chunks_total") == 4
        assert _delta(sched, before,
                      "serve_admit_pair_dispatches_total") == 4
        assert _delta(sched, before,
                      "serve_prefill_tokens_padded_total") == 2 * 128
    finally:
        sched.stop()


# -- the width changes nothing a request can see ------------------------------------

@pytest.mark.parametrize("family,config", [(llama, CFG), (mixtral, MOE_CFG)],
                         ids=["dense", "moe"])
@pytest.mark.parametrize("prompt", [SHORT, LONG, HEAD + LONG],
                         ids=["single-shot", "ladder", "prefix-ladder"])
@pytest.mark.parametrize("kv_quant", [False, True],
                         ids=["paged", "paged-int8"])
def test_stream_at_one_row_equals_stream_padded_to_eight(family, config,
                                                         prompt, kv_quant):
    params = (PARAMS if config is CFG else
              family.init_params(config, jax.random.PRNGKey(0),
                                 dtype=jnp.float32))
    one = _scheduler(params, config, prefix_cache=True, kv_quant=kv_quant)
    eight = _scheduler(params, config, prefix_cache=True, admit_chunk=8,
                       kv_quant=kv_quant)
    try:
        for s in (one, eight):
            assert s.register_prefix(HEAD) > 0
        a0, b0 = one.metrics_snapshot(), eight.metrics_snapshot()
        for opts in (GREEDY, GenerateOptions(max_tokens=8, temperature=0.8,
                                             top_p=0.9, seed=5)):
            assert _run(one, prompt, opts) == _run(eight, prompt, opts)
        assert _delta(one, a0, "serve_admit_rows_padded_total") == 2
        assert _delta(eight, b0, "serve_admit_rows_padded_total") == 16
    finally:
        one.stop()
        eight.stop()


def test_dense_logits_at_one_row_match_the_first_row_of_eight():
    S, n = 64, 50
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, S), 3,
                              CFG.vocab_size)
    # The request first, seven dummy entries (one-token prompts of id 0)
    # behind it: the layout _admit_host_arrays builds.
    padded = jnp.concatenate([toks, jnp.zeros((7, S), jnp.int32)])
    lens = jnp.asarray([n] + [1] * 7, jnp.int32)
    prefill = jit_model(llama.prefill, CFG, last_only=True)
    lg1, c1 = prefill(PARAMS, toks, lens[:1],
                      KVCache.create(CFG, 1, S, dtype=jnp.float32))
    lg8, c8 = prefill(PARAMS, padded, lens,
                      KVCache.create(CFG, 8, S, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(lg1[0]), np.asarray(lg8[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c1.k[:, 0, :n]),
                               np.asarray(c8.k[:, 0, :n]),
                               rtol=1e-5, atol=1e-5)
    assert int(jnp.argmax(lg1[0, 0])) == int(jnp.argmax(lg8[0, 0]))


def test_requests_come_before_the_dummy_entries():
    """A routed MLP's capacity buckets fill in entry order, so the
    requests must come first: ahead of them, the dummy entries (all
    token 0, all routed alike) took the buckets of their two experts."""
    sched = _scheduler(page_size=16)
    try:
        slots = []
        for i, p in enumerate((SHORT, SHORT + "!")):
            ids = TOK.encode(p, add_bos=True)
            s = sched_mod._Slot(
                req=GenerateRequest(prompt=p, options=GREEDY), stats=None,
                out_q=None, seed=i)
            s.prompt_ids, s.pages = ids, [3 + i]
            slots.append(s)
        tokens, ints, _, _, tables = sched_mod._admit_unpack(
            sched._admit_host_arrays(slots, [5, 2], 32, 8, None),
            sched._cache.max_pages_per_row)
        assert ints[1].tolist() == [5, 2] + [sched.num_slots] * 6
        assert ints[0].tolist() == [len(s.prompt_ids) for s in slots] + [1] * 6
        assert tokens[0, :3].tolist() == slots[0].prompt_ids[:3]
        assert not tokens[2:].any() and not tables[2:].any()
        assert tables[:2, 0].tolist() == [3, 4]
    finally:
        sched.stop()


# -- the warm-up surface ---------------------------------------------------------

def _jobs(shapes, C) -> int:
    """Warm-up jobs of a list of admission shapes: one per single-shot
    program, one per offset of a chunk ladder."""
    return sum(S // C if C and S > C and S % C == 0 else 1
               for _, S, _, _ in shapes)


def test_benchmark_warmup_is_no_more_programs_than_the_old_ladder():
    """The benchmark's warm-up: buckets 128..2048, 32 slots, chunk 256,
    the template's 88-token prefix. {8, 32} was 36 admission programs
    and 8 grain pre-warms (PERF.md §6, PR 24)."""
    config = get_config("tiny-long")
    params = llama.init_params(config, jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    sched = _scheduler(params, config, num_slots=32, max_seq=2048,
                       prefill_chunk=256, prefix_cache=True)
    try:
        shapes = sched._admission_shapes([128, 256, 512, 1024, 2048], {88})
        real = [s for s in shapes if not s[3]]
        assert _jobs(real, 256) <= 36
        assert _jobs(shapes, 256) <= 36 + 8
        # Every bucket has its 1-row program; no dispatch passes the
        # token cap unless it is one row wide.
        for P in (0, 88):
            for S in (128, 256, 512, 1024, 2048):
                if P + S <= 2048:
                    assert (P, S, 1, False) in shapes
        assert all(R == 1 or R * (P + S) <= sched_mod._ADMIT_PAIR_TOKENS
                   for P, S, R, _ in shapes)
    finally:
        sched.stop()


def _compiled(sched) -> int:
    """Programs the admission path has compiled or run so far."""
    return (sched._admit_j._cache_size() + sched._admit_prefix_j._cache_size()
            + len(sched._chunk_shapes_run))


def test_after_warmup_no_admission_the_chooser_can_pick_compiles(monkeypatch):
    """The benchmark's ladder at an eighth of its size: buckets 16..256,
    chunk 32, the 2-row program capped at 256 tokens, a registered
    prefix."""
    monkeypatch.setattr(sched_mod, "_ADMIT_PAIR_TOKENS", 256)
    sched = _scheduler(num_slots=8, prefix_cache=True, page_size=16)
    buckets = (16, 32, 64, 128, 256)
    try:
        sched.warmup(prompt_buckets=buckets, prefix_texts=(HEAD,))
        P = sched._registered_prefix_len(HEAD)
        shapes = sched._admission_shapes(list(buckets), {P})
        assert sched.metrics_snapshot()["serve_boot_programs_total"] >= (
            _jobs(shapes, CHUNK))
        for P0 in (0, P):
            for S in buckets:
                if P0 + S > sched.max_seq:
                    continue
                for n in range(1, sched.num_slots + 1):
                    R = sched._admit_width(n, P0 + S)
                    assert (P0, S, R, False) in shapes
                    if S > CHUNK:
                        assert sched._chunk_ladder_ready(P0, S, R)
        warm = _compiled(sched)
        lone = [SHORT, LONG, HEAD + SHORT, HEAD + LONG, "x" * 200]
        for p in lone:
            _run(sched, p)
        _together(sched, [f"{SHORT} #{i}" for i in range(3)])
        _together(sched, [f"{HEAD}{LONG} #{i}" for i in range(2)])
        assert _compiled(sched) == warm
    finally:
        sched.stop()


def test_dummy_entries_behind_the_request_take_no_expert_capacity():
    """Why the order matters, on a capacity-bounded MoE (factor 2.0, as
    the benchmark's mixtral configuration): with the dummy entries
    first, their tokens fill the buckets of the two experts they all
    agree on and the request's assignments to those experts are
    dropped; behind the request they overflow harmlessly, and the
    request's logits are those of the 1-row program."""
    config = MOE_CFG.with_(num_experts=8, num_experts_per_tok=2,
                           moe_capacity_factor=2.0)
    params = mixtral.init_params(config, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    S, n = 64, 60
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, S), 3,
                              config.vocab_size)
    dummies = jnp.zeros((7, S), jnp.int32)

    prefill = jit_model(mixtral.prefill, config, last_only=True)

    def last_logits(tokens, lens, row):
        cache = KVCache.create(config, tokens.shape[0], S, dtype=jnp.float32)
        lg, _ = prefill(params, tokens, jnp.asarray(lens, jnp.int32), cache)
        return np.asarray(lg[row, 0])

    alone = last_logits(toks, [n], 0)
    first = last_logits(jnp.concatenate([toks, dummies]), [n] + [1] * 7, 0)
    last = last_logits(jnp.concatenate([dummies, toks]), [1] * 7 + [n], 7)
    np.testing.assert_allclose(first, alone, rtol=1e-4, atol=1e-4)
    assert np.max(np.abs(last - alone)) > 100 * np.max(np.abs(first - alone))
    assert np.max(np.abs(last - alone)) > 1e-2

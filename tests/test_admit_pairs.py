"""Two requests collected together share their bucket's 2-row program,
and nothing waits for a partner (PERF.md section 6, PR 39: on the chip
a second row costs a routed model's dispatch 0.8-1.3 of a first, so a
pair is worth a dispatch's fixed part and no more).

- a pair of one bucket is ONE dispatch of two rows, single-shot, behind
  a cached prefix and through a chunk ladder, and
  ``serve_admit_pair_dispatches_total`` counts each dispatch, single-shot
  or chunk, that carried both;
- what each of the two generates is what it generates alone (dense,
  routed and hybrid: pages, recurrent state and the routed layers'
  counts), under the stack the benchmark serves with;
- requests of different buckets do not share: the shorter would ride
  every chunk of the longer one's ladder at the 2-row price;
- with live streams and one row free the head of the queue is admitted
  at once, alone: no row is held for the request behind it.

All on the CPU: counts and control flow, never a device timing.
"""

import threading

import jax
import jax.numpy as jnp
import pytest

from p2p_llm_chat_tpu.models import family_for
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.serve.scheduler import _WarmupJob
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

FAMILIES = ("tiny", "tiny-moe", "tiny-nemotron-h")
CHUNK = 32
HEAD = "one shared head for all, "
# Two prompts a kind, both of one bucket: 32 (one dispatch), 32 behind
# the cached head, 64 (a ladder of two chunks of 32).
PAIRS = {
    "splice": ("are we on for ten?", "see you at noon, ok"),
    "prefix": (HEAD + "and a tail of its own",
               HEAD + "and another one of them"),
    "ladder": ("a prompt of the second bucket, two chunks of it: abc",
               "the other one is shorter but two chunks too"),
}
DISPATCHES = {"splice": 1, "prefix": 1, "ladder": 2}
OPTS = dict(temperature=0.8, top_k=20, top_p=0.9, repeat_penalty=1.3)
MOE_KEYS = ("serve_moe_assignments_total", "serve_moe_dropped_total")


def _build(family, **kw):
    """An engine on the stack the benchmark serves with: int8 weights
    under float32 activations, the int8 page pool, the prefix store, a
    chunk ladder."""
    cfg = get_config(family)
    params = family_for(cfg).init_params_quantized(
        cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    kw.setdefault("num_slots", 4)
    eng = TPUEngine(params, cfg, ByteTokenizer(vocab_size=cfg.vocab_size),
                    max_seq=256, page_size=16, kv_quant=True,
                    prefix_cache=True, decode_fuse_max=1,
                    prefill_chunk=CHUNK, **kw)
    assert eng.scheduler.register_prefix(HEAD) > 0
    # A routed model's prefix build leaves its counts for the next
    # admission's readback: take them out of the cases' way.
    _together(eng.scheduler, [_request("the first of all", 1)])
    return eng


@pytest.fixture(scope="module")
def engines():
    """One engine at a time: the cases run family by family, and a
    family's engine stops when the next one's is asked for."""
    live: dict = {}

    def engine_of(family):
        if family not in live:
            for eng in live.values():
                eng.stop()
            live.clear()
            live[family] = _build(family)
        return live[family]

    yield engine_of
    for eng in live.values():
        eng.stop()


def _request(prompt, seed, max_tokens=6, **opts):
    return GenerateRequest(prompt=prompt, options=GenerateOptions(
        max_tokens=max_tokens, seed=seed, **(opts or OPTS)))


def _together(sched, requests) -> list:
    """Submit ``requests`` so that one collection takes them all: the
    loop is held inside a queued job while they are enqueued. Returns
    each one's generated ids."""
    gate, void = threading.Event(), threading.Event()
    job = _WarmupJob(lambda: gate.wait(timeout=60), void)
    sched._admit_q.put(job)
    stats = [RequestStats() for _ in requests]
    streams = [sched.submit(r, s) for r, s in zip(requests, stats)]
    gate.set()
    assert job.done.wait(timeout=60)
    for s in streams:
        for _ in s:
            pass
    return [s.context[s.prompt_tokens:] for s in stats]


def _delta(sched, before: dict, key: str):
    return sched.metrics_snapshot()[key] - before[key]


@pytest.mark.parametrize("kind", list(PAIRS))
@pytest.mark.parametrize("family", FAMILIES)
def test_a_pair_shares_one_dispatch_and_each_generates_as_alone(
        engines, family, kind):
    sched = engines(family).scheduler
    requests = [_request(p, seed) for p, seed in zip(PAIRS[kind], (7, 9))]
    routed = bool(sched._counted)
    alone, counts = [], [0, 0]
    for r in requests:
        before = sched.metrics_snapshot()
        alone += _together(sched, [r])
        assert _delta(sched, before, "serve_admit_rows_padded_total") == 1
        assert _delta(sched, before,
                      "serve_admit_pair_dispatches_total") == 0
        if routed:
            counts = [c + _delta(sched, before, k)
                      for c, k in zip(counts, MOE_KEYS)]
    before = sched.metrics_snapshot()
    assert _together(sched, requests) == alone
    assert all(alone)
    assert _delta(sched, before, "serve_admitted_total") == 2
    assert _delta(sched, before, "serve_admit_batches_total") == 1
    assert _delta(sched, before, "serve_admit_rows_padded_total") == 2
    assert _delta(sched, before, "prefill_chunks_total") == (
        2 if kind == "ladder" else 0)
    assert _delta(sched, before,
                  "serve_admit_pair_dispatches_total") == DISPATCHES[kind]
    assert _delta(sched, before, "serve_prefix_admits_total") == (
        2 if kind == "prefix" else 0)
    if routed:
        # Two prompts in one dispatch route as they do alone, and the
        # padding behind the shorter one is counted nowhere.
        assert [_delta(sched, before, k) for k in MOE_KEYS] == counts
        assert counts[0] > 0


@pytest.mark.parametrize("family", FAMILIES)
def test_requests_of_different_buckets_do_not_share(engines, family):
    sched = engines(family).scheduler
    requests = [_request(PAIRS["ladder"][0], 7),
                _request(PAIRS["splice"][0], 9)]
    alone = [ids for r in requests for ids in _together(sched, [r])]
    before = sched.metrics_snapshot()
    assert _together(sched, requests) == alone
    assert _delta(sched, before, "serve_admit_batches_total") == 2
    assert _delta(sched, before, "serve_admit_rows_padded_total") == 2
    assert _delta(sched, before, "serve_admit_pair_dispatches_total") == 0
    assert _delta(sched, before, "prefill_chunks_total") == 2


def test_one_free_row_admits_the_head_at_once():
    """Four rows, three long streams and a short one, and two requests
    of one bucket queued behind them: when the short stream ends, the
    head of the queue takes its row alone, and the request behind it
    follows when the next row comes free."""
    eng = _build("tiny", num_slots=4)
    sched = eng.scheduler
    try:
        greedy = dict(temperature=0.0)
        live = [_request(f"{PAIRS['splice'][0]} #{i}", i, max_tokens=40,
                         **greedy) for i in range(3)]
        live.append(_request(PAIRS["splice"][1], 3, max_tokens=4, **greedy))
        queued = [_request(f"{PAIRS['splice'][1]} #{i}", i, max_tokens=4,
                           **greedy) for i in (4, 5)]
        before = sched.metrics_snapshot()
        out = _together(sched, live + queued)
        # The premise: the short stream ended first, alone, and no stop
        # id cut a long one short before the queue had drained.
        assert [len(ids) for ids in out[3:]] == [4, 4, 4]
        assert min(len(ids) for ids in out[:3]) > 3 * 4
        assert _delta(sched, before, "serve_admitted_total") == 6
        # Two pairs at the empty batch, then two requests a row each.
        assert _delta(sched, before, "serve_admit_batches_total") == 4
        assert _delta(sched, before, "serve_admit_rows_padded_total") == 6
        assert _delta(sched, before,
                      "serve_admit_pair_dispatches_total") == 2
    finally:
        eng.stop()

"""Tree-speculation tests (round 17, alongside tests/test_spec.py and
tests/test_spec_draft.py).

The load-bearing properties:

- **Mask + positions**: tree verify is ONE forward where every node
  attends the committed prefix plus its own root-to-node ancestor path
  (llama.tree_attention_mask), at RoPE position lengths + depth — so
  each node's logits equal the sequential decode that walked its path.
- **Exactness**: greedy serving output is BIT-identical with tree
  speculation on vs off, INCLUDING ticks where a sibling leaf is
  accepted (the sibling is only taken when it IS the penalized argmax,
  so it equals the linear correction; the follow-up correction from
  the sibling node's own logits equals the next sequential argmax).
- **Containment**: rejected-branch kv slots sit past the accepted
  path's slots, so they stay stale-beyond-length — the committed
  region is bit-untouched by a tree verify.
- **One drafter dispatch per spec tick**: catch-up feed + K draft
  steps + runner-up capture ride ONE device launch (the tree's branch
  signal must not add drafter dispatches over linear).
- **Budget win**: at the SAME verify budget (node count), sibling
  leaves convert first-rejection ticks into +1 accepted — accepted
  tokens per verify dispatch strictly above the linear chain's on a
  workload whose drafter misses at a known position.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.models.synth import quote_params, successor_map
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer
from p2p_llm_chat_tpu.utils.draft import DraftSource, NGramSource

from solo import Solo

pytestmark = pytest.mark.model

CFG = get_config("tiny")
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
FREEFORM = quote_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32,
                        mode="freeform")
SUCC = successor_map(CFG.vocab_size, mode="freeform")
DCFG = CFG.with_(num_layers=1, name="tiny-draft")
DRAFT_FF = quote_params(DCFG, jax.random.PRNGKey(1), dtype=jnp.float32,
                        mode="freeform")
PROMPT = "Tell me something new about the harbor lights"


# The sequential greedy loop on the model layer's dense cache
# (tests/solo.py).
greedy_oracle = Solo(llama, CFG, TOK, max_seq=256)


class CorruptMainSource(DraftSource):
    """Deterministic sibling-exercising source: walks the freeform
    successor cycle (the target's exact greedy path) but corrupts main
    position 1; tree mode carries the TRUE token as the second choice
    there (gap 0 — always a branch site). Linear spec therefore accepts
    exactly 1 draft per tick; tree spec accepts 2 (main + sibling) —
    a controlled first-rejection workload for the on/off oracle and
    the budget A/B."""

    name = "corrupt"

    def __init__(self, k: int) -> None:
        self.k = k

    def _walk(self, ctx) -> list[int]:
        prompt, ids = ctx
        t = (ids or list(prompt))[-1]
        out = []
        for _ in range(self.k):
            t = int(SUCC[t])
            out.append(t)
        return out

    def draft_batch(self, rows, ctxs):
        out = {}
        for r in rows:
            main = self._walk(ctxs[r])
            if len(main) > 1:
                main[1] = (main[1] + 1 - 32) % 95 + 32   # wrong, printable
            out[r] = main
        return out

    def draft_tree_batch(self, rows, ctxs):
        out = {}
        for r in rows:
            true = self._walk(ctxs[r])
            main = list(true)
            if len(main) > 1:
                main[1] = (main[1] + 1 - 32) % 95 + 32
            out[r] = (main, true, [0.0] * len(main))
        return out


def install_source(eng: TPUEngine, src: DraftSource) -> None:
    """Swap the scheduler's draft sources for a test source (before any
    traffic — the loop only consults sources on spec ticks)."""
    sch = eng.scheduler
    sch._ensure_sources()
    sch._spec_ema[src.name] = 10.0
    sch._spec_cooldown[src.name] = 0
    sch._n_spec_proposed_src[src.name] = 0
    sch._n_spec_accepted_src[src.name] = 0
    sch._n_spec_dispatch_src[src.name] = 0
    sch._sources[:] = [src]


def run_engine(params, prompt: str, max_new: int, *, draft=None,
               spec_k: int = 4, source=None, **kw) -> tuple[str, dict]:
    eng = TPUEngine(params, CFG, TOK, num_slots=2, max_seq=256,
                    spec_k=spec_k, draft=draft, **kw)
    try:
        if source is not None:
            install_source(eng, source)
        req = GenerateRequest(prompt=prompt,
                              options=GenerateOptions(max_tokens=max_new))
        got = "".join(eng.generate_stream(req, RequestStats()))
        return got, eng.metrics_snapshot()
    finally:
        eng.stop()


# -- mask + positions ---------------------------------------------------------

def test_tree_attention_mask_shape_and_ancestry():
    """Every node sees the committed prefix; node columns follow the
    ancestor sets exactly (self included); everything past the tree is
    masked off."""
    B, N, W = 2, 4, 16
    lengths = jnp.asarray([5, 0], jnp.int32)
    anc = np.zeros((B, N, N), bool)
    # Row 0: chain 0-1-2 plus node 3 = sibling of node 2 (ancestors 0,1).
    for i in range(3):
        anc[0, i, : i + 1] = True
    anc[0, 3, [0, 1, 3]] = True
    anc[1] = np.eye(N, dtype=bool)
    m = np.asarray(llama.tree_attention_mask(lengths, jnp.asarray(anc), W))
    assert m.shape == (B, 1, N, W)
    assert m[0, 0, :, :5].all()              # committed prefix visible
    for i in range(N):                       # node cols == ancestor sets
        np.testing.assert_array_equal(m[0, 0, i, 5: 5 + N], anc[0, i])
    assert not m[0, 0, :, 5 + N:].any()      # beyond the tree: masked
    # Row 1 (length 0): the node window starts at column 0 — each node
    # sees exactly itself (eye ancestry), nothing else.
    np.testing.assert_array_equal(m[1, 0, :, :N], np.eye(N, dtype=bool))
    assert not m[1, 0, :, N:].any()


def test_verify_tree_logits_match_sequential_paths():
    """Each tree node's logits equal the sequential decode that walked
    its root-to-node path — the mask/position construction is exactly
    'K+1 causal chains sharing a prefix', batched."""
    rng = np.random.default_rng(0)
    B, P = 1, 10
    prompt = jnp.asarray(rng.integers(32, 127, (B, P)), jnp.int32)
    cache = KVCache.create(CFG, B, 64, jnp.float32)
    logits, cache = llama.prefill(FREEFORM, CFG, prompt,
                                  jnp.full((B,), P, jnp.int32), cache)
    t0 = int(np.asarray(logits[0, P - 1]).argmax())
    # Chain t0 -> d0 -> d1 plus a sibling s of d1 (depth 2, anc {0,1}).
    d0, d1 = int(SUCC[t0]), int(SUCC[int(SUCC[t0])])
    s = (d1 + 1 - 32) % 95 + 32
    N = 4
    tokens = jnp.asarray([[t0, d0, d1, s]], jnp.int32)
    depths = jnp.asarray([[0, 1, 2, 2]], jnp.int32)
    anc = np.zeros((B, N, N), bool)
    for i in range(3):
        anc[0, i, : i + 1] = True
    anc[0, 3, [0, 1, 3]] = True
    tree_lg, tree_cache = llama.verify_tree(FREEFORM, CFG, tokens, depths,
                                            jnp.asarray(anc), cache)
    # Sequential replay of both paths from the same prefill state.
    for path, nodes in ([(t0, d0, d1), (0, 1, 2)],
                        [(t0, d0, s), (0, 1, 3)]):
        c = jax.tree.map(lambda x: x, cache)
        for tok, node in zip(path, nodes):
            lg, c = llama.decode_step(FREEFORM, CFG,
                                      jnp.asarray([[tok]]), c)
            np.testing.assert_allclose(np.asarray(tree_lg[:, node]),
                                       np.asarray(lg[:, 0]),
                                       atol=2e-4, rtol=2e-4)
    # Containment: the committed region is bit-untouched; writes landed
    # only in the node window [P, P+N).
    np.testing.assert_array_equal(np.asarray(tree_cache.k[:, :, :P]),
                                  np.asarray(cache.k[:, :, :P]))
    np.testing.assert_array_equal(np.asarray(tree_cache.k[:, :, P + N:]),
                                  np.asarray(cache.k[:, :, P + N:]))


# -- exactness: tree on vs off ------------------------------------------------

@pytest.mark.parametrize("kv_quant", [
    False,
    # The int8 leg re-proves the same acceptance + sibling compaction
    # over the quantized pool; tier-1 keeps the float leg.
    pytest.param(True, marks=pytest.mark.slow),
])
def test_greedy_bit_identical_tree_on_off(kv_quant):
    """Bit-identity with tree speculation on vs off, on a workload that
    ACCEPTS a sibling every tick (CorruptMainSource: main chain wrong at
    position 1, truth as the branch) — the accepted-sibling emit, its
    kv compaction, and the sibling-logits correction all on the greedy
    path."""
    want = greedy_oracle(FREEFORM, PROMPT, 24)
    off, _ = run_engine(FREEFORM, PROMPT, 24, source=CorruptMainSource(4),
                        page_size=16, kv_quant=kv_quant)
    on, snap = run_engine(FREEFORM, PROMPT, 24, source=CorruptMainSource(4),
                          spec_tree_nodes=8, page_size=16, kv_quant=kv_quant)
    assert off == want
    assert on == want
    # Mean accepted path length 3 (root + main pos 0 + sibling) proves
    # the sibling leg actually ran — not a linear tick in disguise.
    assert snap["serve_spec_tree_accepted_path_len"] > 2.5
    assert snap["serve_spec_tree_nodes_total"] > 0


def test_greedy_bit_identical_tree_on_off_model_drafter():
    """Tree on/off bit-identity with the REAL resident drafter (freeform
    pair: ~100% acceptance, siblings budgeted from its top-2 gaps) —
    the all-accepted path through the tree program."""
    want = greedy_oracle(FREEFORM, PROMPT, 24)
    on, snap = run_engine(FREEFORM, PROMPT, 24, draft=(DRAFT_FF, DCFG),
                          spec_tree_nodes=8)
    assert on == want
    assert snap["serve_spec_tree_nodes_total"] > 0


# -- drafter protocol ---------------------------------------------------------

def test_ngram_tree_degrades_to_linear_chain():
    """NGramSource has no runner-up score: draft_tree_batch must return
    the draft_batch chain with EMPTY second/gap lists (the scheduler
    budgets no siblings — the tree is a path)."""
    src = NGramSource(k=3)
    ids = [1, 2, 3, 9, 1, 2]
    src.admit(0, ids)
    ctxs = {0: (ids, [])}
    lin = src.draft_batch([0], ctxs)
    tree = src.draft_tree_batch([0], ctxs)
    assert lin[0] == [3, 9, 1]
    assert tree[0] == ([3, 9, 1], [], [])


def test_one_drafter_dispatch_per_spec_tick():
    """A tree spec tick pays ONE drafter launch: catch-up feed + K
    greedy steps + runner-up capture are fused into a single program
    (serve/draft_model._draft_for). Feed-only dispatches happen at
    admission prefill, never between spec ticks."""
    eng = TPUEngine(FREEFORM, CFG, TOK, num_slots=2, max_seq=256,
                    spec_k=4, draft=(DRAFT_FF, DCFG), spec_tree_nodes=8)
    try:
        drafter = eng.scheduler._draft_model
        assert drafter is not None
        warm_feeds = drafter.n_feed_dispatches
        req = GenerateRequest(prompt=PROMPT,
                              options=GenerateOptions(max_tokens=24))
        "".join(eng.generate_stream(req, RequestStats()))
        snap = eng.metrics_snapshot()
        ticks = eng.scheduler._n_spec_dispatch_src["model"]
        assert ticks > 0
        assert drafter.n_draft_dispatches == ticks
        # One admission prefill feed; zero catch-up feeds between ticks.
        assert drafter.n_feed_dispatches == warm_feeds + 1
        assert snap["serve_spec_tree_nodes_total"] > 0
    finally:
        eng.stop()


# -- budget win ---------------------------------------------------------------

def test_tree_accepts_more_per_dispatch_than_linear_at_equal_budget():
    """SAME verify budget (8 node positions): linear K=7 vs tree
    K=4/N=8. The drafter misses at main position 1 every tick, so the
    linear chain accepts 1/dispatch no matter how long it is, while the
    tree's sibling converts the miss into a second accepted token."""
    lin, snap_l = run_engine(FREEFORM, PROMPT, 24,
                             source=CorruptMainSource(7), spec_k=7)
    tree, snap_t = run_engine(FREEFORM, PROMPT, 24,
                              source=CorruptMainSource(4), spec_k=4,
                              spec_tree_nodes=8)
    want = greedy_oracle(FREEFORM, PROMPT, 24)
    assert lin == want and tree == want
    lin_apd = snap_l["serve_spec_accepted_per_dispatch"]
    tree_apd = snap_t["serve_spec_accepted_per_dispatch"]
    assert tree_apd > lin_apd
    assert snap_t['serve_spec_accepted_per_dispatch{source="corrupt"}'] \
        > snap_l['serve_spec_accepted_per_dispatch{source="corrupt"}']

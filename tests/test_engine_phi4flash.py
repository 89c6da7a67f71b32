"""tiny-phi4flash (Mamba-1 layers, window attention over rings, one full
layer whose pages the cross layers read, gated memory units) through the
scheduler, end to end on the CPU, on the stack the benchmark serves
with: int8 weights, the paged int8 pool of its ONE page layer, the state
pool with its int8 rings, the prefix store, fused decode and a chunk
ladder. A module of its own, so that its programs are freed before the
next module's (tests/conftest.py)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import family_for, nemotron_h
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops import state_pool
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

from solo import Solo, generate as run, jit_model

CFG = get_config("tiny-phi4flash")
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
# One-shot prefill of the unpadded prompt, K and V spliced into a one-row
# int8 pool, state and rings into its row of the state pool, plain decode
# steps (tests/solo.py).
SOLO = Solo(nemotron_h, CFG, TOK, pool="int8", max_seq=256, last_only=True)


@pytest.fixture(scope="module")
def qparams():
    """int8 weights under float32 activations (tests/test_engine_pangu.py
    says why: in bfloat16 the last bits pick the token)."""
    return nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                            dtype=jnp.float32)


HEAD = "hybrid shared head, "


@pytest.fixture(scope="module")
def engine(qparams):
    """The stack the benchmark serves with, booted once for the module:
    the tests that serve through it read counters as differences between
    snapshots, and only the second registers ``HEAD``."""
    eng = TPUEngine(qparams, CFG, TOK, num_slots=4, max_seq=256,
                    page_size=16, kv_quant=True, prefix_cache=True,
                    prefix_texts=(HEAD,), decode_fuse_max=4,
                    prefill_chunk=32)
    yield eng
    eng.stop()


def test_family_is_the_hybrid_walk_with_no_branch_of_its_own():
    assert family_for(CFG) is nemotron_h
    assert family_for(get_config("phi-4-mini-flash-reasoning")) is nemotron_h
    assert (CFG.num_layers, CFG.ssm_layers, CFG.window_layers,
            CFG.cache_layers) == (8, 3, 2, 1)
    assert not CFG.is_moe


def test_three_lengths_in_one_batch_stream_the_models_greedy_tokens(
        qparams, engine):
    """The scheduler end to end: three requests of different lengths
    (one inside a window, one past several, one a chunk ladder) admitted
    together, cold (nothing is in the prefix store yet), decode in one
    batch and each streams the solo loop's tokens."""
    eng = engine
    assert len(eng.scheduler._prefix) == 0
    m0 = eng.metrics_snapshot()
    prompts = ["hi", "a prompt well past the window of eight",
               "z" * 70]
    got, errs = {}, []

    def worker(p):
        try:
            got[p] = run(eng, p, max_tokens=10)[0]
        except Exception as e:   # noqa: BLE001
            errs.append((p, e))

    threads = [threading.Thread(target=worker, args=(p,)) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not errs, errs
    assert got == {p: SOLO(qparams, p, 10) for p in prompts}
    m = eng.metrics_snapshot()
    assert m["serve_admitted_total"] - m0["serve_admitted_total"] == 3
    assert m["serve_kv_free_pages"] == m["serve_kv_total_pages"]


def test_prefix_hit_chunks_fused_decode_slot_reuse_and_counters(qparams,
                                                                engine):
    """A lone request, a prompt longer than a chunk behind the
    registered head (a prefix hit that starts from the entry's state AND
    ring snapshot, then first / mid / final chunk programs), a cold one
    of the same length, then more requests than slots in turn (every
    slot reused: a tenant that inherited a ring or a state would not
    stream the solo loop's tokens): greedy output equals the solo loop's
    on the unpadded prompt, and the counters count what they say."""
    head, eng = HEAD, engine
    m0 = eng.metrics_snapshot()
    sched = eng.scheduler
    built = sched.register_prefix(head)
    assert built == len(TOK.encode(head, add_bos=True)) - 1
    entry = sched._prefix.snapshot()[0]
    # One page layer's K and V, three Mamba states, two rings.
    assert entry.k.shape[0] == 1
    assert entry.state.ssm.shape == (3, 16, 256)
    assert entry.state.win_k.shape == (2, 1, 8, 64)
    assert entry.nbytes > entry.k.nbytes + entry.v.nbytes
    lone = "a request that arrives alone"
    long = head + "x" * 90          # suffix bucket 128: four chunks
    longer = "y" * 75               # no head, bucket 128: four chunks
    burst = [head + f"burst {i}" for i in range(5)] + [
        f"no head {i}" for i in range(3)]
    assert run(eng, lone, max_tokens=6)[0] == SOLO(qparams, lone, 6)
    assert run(eng, long, max_tokens=6)[0] == SOLO(qparams, long, 6)
    assert run(eng, longer, max_tokens=6)[0] == SOLO(qparams, longer, 6)
    got, errs = {}, []

    def worker(p):
        try:
            got[p] = run(eng, p, max_tokens=9)[0]
        except Exception as e:   # noqa: BLE001
            errs.append((p, e))

    threads = [threading.Thread(target=worker, args=(p,))
               for p in burst]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not errs, errs
    assert got == {p: SOLO(qparams, p, 9) for p in burst}
    m = eng.metrics_snapshot()
    assert m["serve_admitted_total"] - m0["serve_admitted_total"] == 11
    assert m["prefill_chunks_total"] >= 6
    assert m["serve_prefix_admits_total"] >= 6
    assert m["serve_state_snapshots_total"] == \
        m["serve_prefix_admits_total"]
    assert m["decode_fused_ticks_total"] > 0
    pool = sched._cache.state
    assert m["serve_state_pool_bytes"] == pool.nbytes
    steps = m["serve_state_row_steps_total"]
    assert m["serve_state_bytes_total"] == 2 * steps * pool.row_bytes
    live = m["serve_decode_row_steps_total"]
    assert m["serve_state_row_steps_live_total"] == live
    # Every live row-step read a ring of 1..8 positions in each of the
    # two window layers, and its whole context twice (the full layer
    # and the one cross layer) from the one page layer.
    ring = pool.ring_position_bytes
    assert ring == 2 * (4 * 16 + 4)      # K and V, a row and a scale each
    assert 2 * ring * live <= m["serve_window_bytes_total"] \
        <= 2 * ring * live * CFG.sliding_window
    assert m["serve_shared_kv_bytes_total"] == \
        2 * ring * m["serve_attn_context_tokens_total"]
    assert "serve_moe_routed_pairs_total" not in m


def test_prefix_hit_and_cold_admission_give_the_same_logits(qparams):
    """At the model level, without sampling between: a suffix prefilled
    behind a prefix entry's K, V, state and ring snapshot, and the whole
    prompt prefilled cold, give the same last-position logits and the
    same carry."""
    ids = jnp.asarray(np.random.default_rng(5).integers(
        3, 500, (1, 37)), jnp.int32)
    P = 21                                  # past two windows of 8
    cold = KVCache.create(CFG, 1, 37, dtype=jnp.float32)
    want, cold = jit_model(nemotron_h.prefill, CFG, last_only=True)(
        qparams, ids, jnp.asarray([37]), cold)
    pre = KVCache.create(CFG, 1, P, dtype=jnp.float32)
    _, pre = jit_model(nemotron_h.prefill, CFG)(
        qparams, ids[:, :P], jnp.asarray([P]), pre)
    snap = state_pool.snapshot(pre.state)
    # As the scheduler seeds a suffix: the entry's K and V in the carry's
    # first P slots, its state in every row, 3 padding positions behind.
    S = 19
    small = KVCache.create(CFG, 1, P + S, dtype=jnp.float32)
    small = small._replace(k=small.k.at[:, :, :P].set(pre.k),
                           v=small.v.at[:, :, :P].set(pre.v),
                           state=state_pool.from_snapshot(snap, 1))
    toks = jnp.pad(ids[:, P:], ((0, 0), (0, S - 16)))
    valid = jnp.arange(S)[None, :] < 16
    got, small, _ = jit_model(nemotron_h.forward_counted, CFG,
                              last_idx=jnp.asarray([15]))(
        qparams, toks, None, small, None, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    for a, b in zip(small.state, cold.state):
        if a is not None:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)


@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "speculative decoding"),
    (dict(kv_host_gb=0.5), "session parking"),
    (dict(mesh="a mesh"), "a mesh"),
])
def test_paths_that_assume_pages_alone_refuse_by_name(qparams, kw, what):
    from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
    with pytest.raises(ValueError,
                       match=f"tiny-phi4flash keeps recurrent state.*{what}"):
        BatchScheduler(qparams, CFG, TOK, num_slots=2, max_seq=64,
                       page_size=16, **kw)


def test_prefix_entries_do_not_travel(qparams):
    eng = TPUEngine(qparams, CFG, TOK, num_slots=2, max_seq=64,
                    page_size=16, prefix_cache=True, prefix_texts=())
    try:
        assert eng.prefix_hashes() is None
        for call in (lambda: eng.prefix_export("00"),
                     lambda: eng.prefix_import(b"")):
            with pytest.raises(ValueError,
                               match="tiny-phi4flash keeps recurrent "
                                     "state.*not exported or imported"):
                call()
    finally:
        eng.stop()

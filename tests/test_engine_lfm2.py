"""tiny-lfm2 (gated short convolutions that keep a two-position window a
row, GQA layers at a head of 64 over their own page layers with the KV
heads in pairs, dense and biased-sigmoid routed feed-forwards) through the
scheduler, end to end on the CPU, on the stack the benchmark serves with:
int8 weights, the paged int8 pool of its page layers, the state pool with
its convolution rows and nothing recurrent, the prefix store, fused decode
and a chunk ladder. A module of its own, so that its programs are freed
before the next module's (tests/conftest.py)."""

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

CFG = get_config("tiny-lfm2")
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
# One-shot prefill of the unpadded prompt, K and V spliced into a one-row
# int8 pool, windows into its row of the state pool, plain decode
# steps (tests/solo.py).
SOLO = Solo(nemotron_h, CFG, TOK, pool="int8", max_seq=256, last_only=True)


@pytest.fixture(scope="module")
def qparams():
    """int8 weights under float32 activations (tests/test_engine_pangu.py
    says why: in bfloat16 the last bits pick the token)."""
    return nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                            dtype=jnp.float32)


HEAD = "lfm2 shared head, "


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
    assert (CFG.num_layers, CFG.ssm_layers, CFG.short_conv_layers,
            CFG.window_layers, CFG.cache_layers, CFG.routed_layers,
            CFG.state_layers) == (30, 0, 10, 0, 5, 13, 10)
    assert CFG.is_moe and CFG.attn_rope and CFG.kv_paired
    assert CFG.state_kinds == "convolution windows (10 layers)"
    assert get_config("tiny-phi4flash").state_kinds == \
        "recurrent state (3 Mamba layers) and window rings (2 layers)"
    assert get_config("tiny-mellum2").state_kinds == \
        "window rings (6 layers)"


def test_three_lengths_in_one_batch_stream_the_models_greedy_tokens(
        qparams, engine):
    """The scheduler end to end: three requests of different lengths
    (one of the window's two positions, one past it, one a chunk ladder) admitted
    together, cold (nothing is in the prefix store yet), decode in one
    batch and each streams the solo loop's tokens."""
    eng = engine
    assert len(eng.scheduler._prefix) == 0
    m0 = eng.metrics_snapshot()
    prompts = ["h", "a prompt well past the window of two",
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
    """A lone request, a prompt longer than a chunk behind the registered
    head (a prefix hit that starts from the entry's window snapshot, then
    first / mid / final chunk programs), a cold one of the same length,
    then more requests than slots in turn, long ones before short ones
    (every slot reused: a tenant that inherited a window would not
    stream the solo loop's tokens): greedy output equals the
    solo loop's on the unpadded prompt, and the counters count what they
    say."""
    head, eng = HEAD, engine
    m0 = eng.metrics_snapshot()
    sched = eng.scheduler
    built = sched.register_prefix(head)
    assert built == len(TOK.encode(head, add_bos=True)) - 1
    entry = sched._prefix.snapshot()[0]
    # Five page layers' K and V with the KV heads in pairs, ten
    # windows, no recurrent state, no rings.
    assert entry.k.shape == (5, built, 2, 128)
    assert entry.state.ssm.size == 0 and entry.state.win_k is None
    assert entry.state.conv.shape == (10, 2, 128)
    assert entry.nbytes > entry.k.nbytes + entry.v.nbytes
    lone = "a request that arrives alone"
    long = head + "x" * 90          # suffix bucket 128: four chunks
    longer = "y" * 75               # no head, bucket 128: four chunks
    burst = [head + "a long tenant " * 4 + str(i) for i in range(4)] + [
        f"t{i}" for i in range(4)]
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
    assert m["serve_prefix_admits_total"] >= 5
    assert m["decode_fused_ticks_total"] > 0
    assert m["serve_moe_dropped_total"] == 0
    assert m["serve_moe_assignments_total"] > 0
    # The windows count under the state pool's series; no rings and no
    # page layer that others read, so none of those.
    assert "serve_window_bytes_total" not in m
    assert "serve_shared_kv_bytes_total" not in m
    pool = sched._cache.state
    row = 10 * 2 * 128 * 4                   # float32 windows here
    assert pool.row_bytes == row
    assert m["serve_state_pool_bytes"] == 5 * row
    assert m["serve_state_snapshots_total"] \
        - m0["serve_state_snapshots_total"] >= 5
    # One program updates every slot's windows a step; the live rows'
    # alone change.
    assert m["serve_state_row_steps_total"] % 4 == 0
    assert 0 < m["serve_state_row_steps_live_total"] \
        == m["serve_decode_row_steps_total"] \
        <= m["serve_state_row_steps_total"]
    assert m["serve_state_bytes_total"] == \
        2 * row * m["serve_state_row_steps_total"]
    # Every live row-step read its whole context from each of the five
    # page layers, once: K and V of 4 heads x 64 in int8 and a float32
    # scale for each of the 2 pairs.
    token = 2 * (4 * 64 + 2 * 4)
    assert m["serve_page_kv_bytes_total"] == \
        5 * token * m["serve_attn_context_tokens_total"]
    assert m["paged_flash_min_w"] == 0           # the CPU runs no kernel


def test_prefix_hit_and_cold_admission_give_the_same_logits(qparams):
    """At the model level, without sampling between: a suffix prefilled
    behind a prefix entry's K, V and window snapshot, and the whole prompt
    prefilled cold, give the same last-position logits and the same
    carry."""
    ids = jnp.asarray(np.random.default_rng(5).integers(
        3, 500, (1, 37)), jnp.int32)
    P = 21
    cold = KVCache.create(CFG, 1, 37, dtype=jnp.float32)
    want, cold = jit_model(nemotron_h.prefill, CFG, last_only=True)(
        qparams, ids, jnp.asarray([37]), cold)
    pre = KVCache.create(CFG, 1, P, dtype=jnp.float32)
    _, pre = jit_model(nemotron_h.prefill, CFG)(
        qparams, ids[:, :P], jnp.asarray([P]), pre)
    snap = state_pool.snapshot(pre.state)
    # As the scheduler seeds a suffix: the entry's K and V in the carry's
    # first P slots, its windows in every row, 3 padding positions behind.
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
    np.testing.assert_allclose(np.asarray(small.state.conv),
                               np.asarray(cold.state.conv), atol=2e-5)


@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "speculative decoding"),
    (dict(kv_host_gb=0.5), "session parking"),
    (dict(mesh="a mesh"), "a mesh"),
])
def test_paths_that_assume_pages_alone_refuse_by_name(qparams, kw, what):
    from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
    with pytest.raises(ValueError,
                       match=r"tiny-lfm2 keeps convolution windows \(10 "
                             rf"layers\) beside its pages.*{what}"):
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
                               match="tiny-lfm2 keeps convolution windows.*"
                                     "not exported or imported"):
                call()
    finally:
        eng.stop()

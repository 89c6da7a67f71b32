"""tiny-nemotron-h (Mamba-2 layers, attention without rotary embedding,
LatentMoE layers holding 4 of 16 sigmoid-scored experts beside a shared
one) through the scheduler, end to end on the CPU, on the stack the
benchmark serves with: int8 weights, the paged int8 pool of its
attention layers AND the state pool of its Mamba layers, the prefix
store, fused decode and a chunk ladder. A module of its own, so that its
programs are freed before the next module's (tests/conftest.py)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import family_for, moe_tiles, nemotron_h
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops import state_pool
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

from solo import Solo, generate as run, jit_model

CFG = get_config("tiny-nemotron-h")
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
# One-shot prefill of the unpadded prompt, K and V spliced into a one-row
# int8 pool and the state into its row of the state pool, plain decode
# steps (tests/solo.py).
SOLO = Solo(nemotron_h, CFG, TOK, pool="int8", max_seq=256, last_only=True)


@pytest.fixture(scope="module")
def qparams():
    """int8 weights under float32 activations (tests/test_engine_pangu.py
    says why: in bfloat16 the last bits pick the token)."""
    return nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                            dtype=jnp.float32)


def test_family_and_pool_geometry():
    assert family_for(CFG) is nemotron_h
    assert (CFG.num_layers, CFG.ssm_layers, CFG.cache_layers) == (11, 5, 1)
    big = get_config("nemotron-3-super-120b-a12b-l22e128")
    assert (big.ssm_layers, big.cache_layers) == (10, 2)
    assert (big.mamba_inner, big.conv_dim) == (8192, 10240)
    assert big.router_width == 512 and big.num_experts == 128
    pool = PagedKVCache.create(CFG, 3, 5, 16, quantized=True)
    # Pages for the attention layer alone; state rows for 3 slots and a
    # garbage row.
    assert pool.k.shape == (1, 5, 16, 2, 32)
    assert pool.state.ssm.shape == (5, 4, 8, 16, 16)
    assert pool.state.ssm.dtype == jnp.float32
    assert pool.state.conv.shape == (5, 4, 3, 8 * 16 + 2 * 2 * 16)
    small = KVCache.create(CFG, 2, 24)
    assert small.k.shape[0] == 1 and small.state.ssm.shape[:2] == (5, 2)
    # The other families' caches carry no state leaf.
    assert KVCache.create(get_config("tiny"), 2, 8).state is None
    assert PagedKVCache.create(get_config("tiny-moe"), 2, 5, 16).state is None


def test_admission_chunks_prefix_fused_decode_slot_reuse_and_counters(
        qparams):
    """A lone request, a prompt longer than a chunk (first / mid / final
    chunk programs carrying the state), a burst sharing the registered
    head (prefix admission from its state snapshot) beside prompts that
    do not, then more requests than slots in turn (every slot reused):
    greedy output equals the solo loop's on the unpadded prompt, and the
    counters count what they say."""
    head = "hybrid shared head, "
    eng = TPUEngine(qparams, CFG, TOK, num_slots=4, max_seq=256,
                    page_size=16, kv_quant=True, prefix_cache=True,
                    prefix_texts=(head,), decode_fuse_max=4,
                    prefill_chunk=32)
    try:
        sched = eng.scheduler
        built = sched.register_prefix(head)
        assert built == len(TOK.encode(head, add_bos=True)) - 1
        entry = sched._prefix.snapshot()[0]
        assert entry.state.ssm.shape == (5, 8, 16, 16)
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
        # Eight requests on four slots: every slot was freed and reused,
        # after occupants of other lengths.
        assert got == {p: SOLO(qparams, p, 9) for p in burst}
        m = eng.metrics_snapshot()
        assert m["serve_admitted_total"] == 11
        assert m["prefill_chunks_total"] >= 6
        assert m["serve_prefix_admits_total"] >= 6
        assert m["serve_state_snapshots_total"] == \
            m["serve_prefix_admits_total"]
        assert m["decode_fused_ticks_total"] > 0
        pool = sched._cache.state
        assert m["serve_state_pool_bytes"] == pool.nbytes
        assert m["serve_state_rows_in_use"] == 0
        steps = m["serve_state_row_steps_total"]
        assert steps > 0 and steps % 4 == 0
        assert m["serve_state_row_steps_live_total"] == \
            m["serve_decode_row_steps_total"]
        assert 0 < m["serve_state_row_steps_live_total"] <= steps
        assert m["serve_state_bytes_total"] == 2 * steps * pool.row_bytes
        per_token = CFG.num_experts_per_tok * CFG.hybrid_pattern.count("E")
        assert m["serve_moe_routed_pairs_total"] == per_token * (
            m["serve_prefill_tokens_total"] + built
            + m["serve_decode_row_steps_total"])
        assert 0 < m["serve_moe_local_pairs_total"] < (
            m["serve_moe_routed_pairs_total"])
        assert m["serve_moe_dropped_total"] == 0
        # Where the decode kernel is the update (on the chip; the boot
        # reads ops/state_pool.ssm_kernel_covers once) only live rows'
        # state moves, and the counters say so: the program is XLA's
        # here, the flag is steered.
        assert sched._state_kernel is False
        sched._state_kernel = True
        assert run(eng, lone, max_tokens=6)[0] == SOLO(qparams, lone, 6)
        after = eng.metrics_snapshot()
        moved = after["serve_state_row_steps_total"] - steps
        assert 0 < moved == (after["serve_state_row_steps_live_total"]
                             - m["serve_state_row_steps_live_total"])
        assert after["serve_state_bytes_total"] \
            - m["serve_state_bytes_total"] == 2 * moved * pool.row_bytes
    finally:
        eng.stop()


def test_prefill_layer_counters_over_windows(qparams):
    """``serve_moe_prefill_layers_total`` is the routed layers of every
    prefill dispatch that carried a request (an admission, each chunk of
    a ladder, a prefix build) and ``serve_moe_prefill_rows_total`` the
    rows their experts multiplied, filled tiles x rows a tile, beside
    ``serve_moe_assignments_total``, the pairs those were for (the
    prefills' share of ``serve_moe_local_pairs_total``), as differences
    between snapshots: never fewer rows than pairs, never more than a
    part-filled tile a held expert a layer over them. (Until PR 43 the
    second counter here was the layers that ran all-T buckets.)
    Decode's use of the same entry of the counts (pairs to held
    experts) goes on counting pairs beside it."""
    head = "hybrid shared head, "
    eng = TPUEngine(qparams, CFG, TOK, num_slots=4, max_seq=256,
                    page_size=16, kv_quant=True, prefix_cache=True,
                    prefix_texts=(), decode_fuse_max=4, prefill_chunk=32)
    E = CFG.routed_layers
    names = ("serve_moe_prefill_layers_total",
             "serve_moe_prefill_rows_total",
             "serve_moe_assignments_total", "serve_moe_local_pairs_total",
             "prefill_chunks_total", "serve_decode_row_steps_total")

    def window(m0):
        m1 = eng.metrics_snapshot()
        return [m1[n] - m0[n] for n in names], m1

    def padding(rows, held, layers):
        """Rows over pairs: at least 1, at most a part-filled tile (of
        under 128 rows) an expert a layer more."""
        assert held <= rows < held + layers * CFG.num_experts * 128
        assert rows % 8 == 0

    try:
        m = eng.metrics_snapshot()
        assert m[names[0]] == m[names[1]] == 0
        run(eng, "hi", max_tokens=6)
        (layers, rows, held, local, chunks, steps), m = window(m)
        assert (layers, chunks) == (E, 0)
        padding(rows, held, layers)
        # Three real positions: 9 pairs a layer at the most, so no
        # expert's run passes one tile.
        assert 0 < held <= 3 * CFG.num_experts_per_tok * E
        assert rows <= E * CFG.num_experts * moe_tiles.tile_rows(
            32 * CFG.num_experts_per_tok, CFG.num_experts, CFG.router_width)
        per_token = CFG.num_experts_per_tok * E
        assert 0 < local - held < per_token * steps
        # A ladder of four chunks, the last of them mostly padding.
        run(eng, "y" * 110, max_tokens=2)
        (layers, rows, held, _, chunks, _), m = window(m)
        assert chunks == 4 and layers == 4 * E
        padding(rows, held, layers)
        # A prefix build's counts wait for the next admission's read.
        eng.scheduler.register_prefix(head)
        assert window(m)[0][:2] == [0, 0]
        run(eng, head + "ok", max_tokens=2)
        (layers, rows, held, _, chunks, _), m = window(m)
        assert chunks == 0 and layers == 2 * E
        padding(rows, held, layers)
    finally:
        eng.stop()


def test_promoted_prefix_serves_from_its_snapshot(qparams):
    """A head seen twice is promoted at a grain (64 tokens) with its
    state snapshot; the third request admits through it and equals the
    uncached solo loop."""
    eng = TPUEngine(qparams, CFG, TOK, num_slots=2, max_seq=256,
                    page_size=16, kv_quant=True, prefix_cache=True,
                    prefix_texts=())
    try:
        head = "z y x w v u t s r q " * 5        # 100 chars -> grain 64
        prompts = [head + tail for tail in ("alpha", "beta", "gamma")]
        store = eng.scheduler._prefix
        for i, p in enumerate(prompts):
            assert run(eng, p, max_tokens=8)[0] == SOLO(qparams, p, 8)
            if i == 1:
                deadline = time.monotonic() + 60
                while len(store) < 1 and time.monotonic() < deadline:
                    time.sleep(0.02)
        assert len(store) == 1
        assert store.snapshot()[0].length == 64
        assert store.snapshot()[0].state is not None
        m = eng.scheduler.metrics_snapshot()
        assert m["serve_prefix_admits_total"] >= 1
        assert m["serve_state_snapshots_total"] >= 1
    finally:
        eng.stop()


def test_decode_leaves_a_free_rows_state_bit_equal(qparams):
    """Decoding other rows moves nothing of a row that is not live."""
    B = 3
    pool = PagedKVCache.create(CFG, B, 13, 16, max_pages_per_row=4,
                               dtype=jnp.float32, quantized=True)
    key = jax.random.PRNGKey(0)
    st = pool.state
    pool = pool._replace(
        state=state_pool.StatePool(
            ssm=jax.random.normal(key, st.ssm.shape, jnp.float32),
            conv=jax.random.normal(key, st.conv.shape, jnp.float32)),
        page_table=1 + jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4),
        lengths=jnp.asarray([5, 7, 9], jnp.int32))
    before = pool.state
    active = jnp.asarray([True, False, True])
    _, after = jit_model(nemotron_h.decode_step_paged, CFG, active=active,
                         pages=4)(qparams, jnp.asarray([[3], [4], [5]]), pool)
    for b, a in ((before.ssm, after.state.ssm),
                 (before.conv, after.state.conv)):
        b, a = np.asarray(b), np.asarray(a)
        assert np.array_equal(b[:, 1], a[:, 1])      # the parked row
        assert np.array_equal(b[:, 3], a[:, 3])      # the garbage row
        assert not np.array_equal(b[:, 0], a[:, 0])
        assert not np.array_equal(b[:, 2], a[:, 2])
    assert list(np.asarray(after.lengths)) == [6, 7, 10]


@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "speculative decoding"),
    (dict(kv_host_gb=0.5), "session parking"),
    (dict(mesh="a mesh"), "a mesh"),
])
def test_paths_that_assume_pages_alone_refuse_by_name(qparams, kw, what):
    from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
    with pytest.raises(ValueError,
                       match=f"tiny-nemotron-h keeps recurrent state.*{what}"):
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
                               match="tiny-nemotron-h keeps recurrent "
                                     "state.*not exported or imported"):
                call()
    finally:
        eng.stop()

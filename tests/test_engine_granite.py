"""tiny-granite-h (Mamba-2 layers and attention without positional
encoding at a head of 64, an MLP behind each, four scalars) through the
scheduler, end to end on the CPU, on the stack the benchmark serves with:
int8 weights, the paged int8 pool of its attention layers (KV heads in
pairs) AND the state pool of its Mamba layers, the prefix store, fused
decode and a chunk ladder, at FORTY slots: more rows than any other test
gives a scheduler, because a family whose row costs the same at every
length is deployed for its rows. A module of its own, so that its
programs are freed before the next module's (tests/conftest.py)."""

import math
import threading

import jax
import jax.numpy as jnp
import pytest

from p2p_llm_chat_tpu.models import family_for, nemotron_h
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache
from p2p_llm_chat_tpu.ops.state_pool import StatePool
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

from solo import Solo, generate as run

CFG = get_config("tiny-granite-h")
BIG = get_config("granite-4.0-h-micro")
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
SOLO = Solo(nemotron_h, CFG, TOK, pool="int8", max_seq=256, last_only=True)
SLOTS = 40


@pytest.fixture(scope="module")
def qparams():
    """int8 weights under float32 activations (tests/test_engine_pangu.py
    says why: in bfloat16 the last bits pick the token)."""
    return nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                            dtype=jnp.float32)


def _nbytes(a) -> int:
    return math.prod(a.shape) * jnp.dtype(a.dtype).itemsize


def test_family_and_pool_geometry():
    assert family_for(CFG) is nemotron_h and family_for(BIG) is nemotron_h
    assert (CFG.num_layers, CFG.ssm_layers, CFG.cache_layers) == (8, 6, 2)
    assert CFG.kv_paired and (CFG.cache_kv_heads, CFG.cache_k_dim) == (1, 128)
    pool = PagedKVCache.create(CFG, 3, 5, 16, quantized=True)
    assert pool.k.shape == (2, 5, 16, 1, 128)
    assert pool.state.ssm.shape == (6, 4, 4, 16, 16)
    assert pool.state.ssm.dtype == jnp.float32
    assert pool.state.conv.shape == (6, 4, 3, 4 * 16 + 2 * 16)
    # The published model: 36 state layers to 4 page layers, the KV heads
    # in four pairs, ONE group of B and C behind a convolution over 4,352
    # channels, a gated norm over one group of 4,096.
    assert (BIG.num_layers, BIG.ssm_layers, BIG.cache_layers) == (40, 36, 4)
    assert BIG.kv_paired and (BIG.cache_kv_heads, BIG.cache_k_dim) == (4, 128)
    assert (BIG.mamba_inner, BIG.conv_dim, BIG.ssm_groups) == (4096, 4352, 1)
    assert BIG.ssm_state_shape == (64, 64, 128)
    assert (BIG.embedding_multiplier, BIG.residual_multiplier,
            BIG.attention_multiplier, BIG.logits_scaling) == (
                12.0, 0.22, 0.015625, 8.0)


def test_the_published_pool_at_64_slots_by_shape():
    """What ``serve_state_bytes_total`` adds a moved row a step at the
    published widths, and the pool's size, from the shapes alone (the
    4.97 GB pool is never allocated here): a row is 36 x (2,097,152 +
    26,112) bytes, a full fused dispatch of 4 steps moves 2 x 64 x 4 of
    them."""
    shapes = jax.eval_shape(
        lambda: StatePool.create(BIG, 65, jnp.bfloat16))
    assert shapes.ssm.shape == (36, 65, 64, 64, 128)
    assert shapes.conv.shape == (36, 65, 3, 4352)
    total = _nbytes(shapes.ssm) + _nbytes(shapes.conv)
    row = total // 65
    assert row == 36 * (2_097_152 + 26_112) == 76_437_504
    assert round(total / 1e9, 2) == 4.97
    # The scheduler's arithmetic at a dispatch (serve/scheduler.py
    # ``_decode_dispatch``: 2 x rows moved x row bytes, rows moved =
    # live rows x fused steps).
    assert 2 * (64 * 4) * row == 2 * 64 * 36 * (2_097_152 + 26_112) * 4
    # The decode kernel takes the shape: blocks of 32 heads, two a row.
    from p2p_llm_chat_tpu.ops import state_pool
    assert state_pool.head_blocks(64, 1)[:3] == [64, 32, 16]
    assert state_pool.pick_head_block(64, 64, 128, 1) == 32


def test_forty_slots_chunks_prefix_fused_decode_and_counters(qparams):
    """A lone request, a prompt longer than a chunk, then 52 requests at
    once on 40 slots, most sharing the registered head (prefix admission
    from its state snapshot): greedy output equals the solo loop's on the
    unpadded prompt, every slot is used and some reused, and the state
    counters count what they say."""
    head = "granite shared head, "
    eng = TPUEngine(qparams, CFG, TOK, num_slots=SLOTS, max_seq=256,
                    page_size=16, kv_quant=True, prefix_cache=True,
                    prefix_texts=(head,), decode_fuse_max=4,
                    prefill_chunk=32)
    try:
        sched = eng.scheduler
        assert sched.num_slots == SLOTS
        pool = sched._cache.state
        assert pool.ssm.shape[:2] == (6, SLOTS + 1)
        built = sched.register_prefix(head)
        assert built == len(TOK.encode(head, add_bos=True)) - 1
        entry = sched._prefix.snapshot()[0]
        assert entry.state.ssm.shape == (6, 4, 16, 16)
        m0 = eng.metrics_snapshot()
        # One snapshot: a row's state and window, whatever the head's
        # length.
        assert m0["serve_prefix_state_bytes"] == pool.row_bytes \
            == entry.state.nbytes
        lone = "a request that arrives alone"
        long = head + "x" * 90          # suffix bucket 128: four chunks
        assert run(eng, lone, max_tokens=6)[0] == SOLO(qparams, lone, 6)
        assert run(eng, long, max_tokens=6)[0] == SOLO(qparams, long, 6)
        burst = [head + f"draft {i} " + "z" * (i % 7) for i in range(44)] \
            + [f"no head {i}" for i in range(8)]
        got, errs = {}, []

        def worker(p, n):
            try:
                got[p] = run(eng, p, max_tokens=n)[0]
            except Exception as e:   # noqa: BLE001
                errs.append((p, e))

        threads = [threading.Thread(target=worker, args=(p, 5 + i % 6))
                   for i, p in enumerate(burst)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not errs, errs
        assert got == {p: SOLO(qparams, p, 5 + i % 6)
                       for i, p in enumerate(burst)}
        m = eng.metrics_snapshot()
        assert m["serve_admitted_total"] == 54
        assert m["prefill_chunks_total"] >= 4
        assert m["serve_prefix_admits_total"] >= 44
        assert m["serve_state_snapshots_total"] == \
            m["serve_prefix_admits_total"]
        assert m["decode_fused_ticks_total"] > 0
        assert m["serve_state_pool_bytes"] == pool.nbytes
        assert m["serve_state_rows_in_use"] == 0
        # Every entry of every admission, dummies too, writes a whole
        # row into the pool, the single-shot programs and a ladder's last
        # chunk alike: ``state_install_share`` reads these entries x the
        # gauge's row.
        assert m["serve_admit_rows_padded_total"] >= 54
        assert m["serve_state_pool_bytes"] == (SLOTS + 1) * pool.row_bytes
        # XLA's update moves every slot's row a step (40 of them), the
        # kernel's count is steered below.
        steps = m["serve_state_row_steps_total"]
        assert steps > 0 and steps % SLOTS == 0
        assert m["serve_state_row_steps_live_total"] == \
            m["serve_decode_row_steps_total"]
        assert 0 < m["serve_state_row_steps_live_total"] <= steps
        assert m["serve_state_bytes_total"] == 2 * steps * pool.row_bytes
        assert m["serve_prefix_state_bytes"] == pool.row_bytes
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


def test_more_than_thirty_two_rows_decode_together(qparams):
    """Forty requests whose outputs (200 tokens) outlast the forty
    admissions, one or two a loop iteration with a fused dispatch of four
    steps between them: the batch passes 32 live rows (no other test's
    scheduler has as many slots), and each streams what it streams
    alone."""
    eng = TPUEngine(qparams, CFG, TOK, num_slots=SLOTS, max_seq=256,
                    page_size=16, kv_quant=True, prefix_cache=False,
                    decode_fuse_max=4, prefill_chunk=32)
    try:
        prompts = [f"member {i} asks for a recap" for i in range(SLOTS)]
        got, errs, peak = {}, [], [0]
        done = threading.Event()

        def watch():
            while not done.is_set():
                peak[0] = max(peak[0], sum(
                    s is not None for s in eng.scheduler._slots))
                done.wait(0.002)

        def worker(p):
            try:
                got[p] = run(eng, p, max_tokens=200)[0]
            except Exception as e:   # noqa: BLE001
                errs.append((p, e))

        watcher = threading.Thread(target=watch)
        watcher.start()
        threads = [threading.Thread(target=worker, args=(p,))
                   for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        done.set()
        watcher.join()
        assert not errs, errs
        assert peak[0] > 32, peak
        for p in prompts[::5]:
            assert got[p] == SOLO(qparams, p, 200), p
    finally:
        eng.stop()


@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "speculative decoding"),
    (dict(kv_host_gb=0.5), "session parking"),
])
def test_paths_that_assume_pages_alone_refuse_by_name(qparams, kw, what):
    from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
    with pytest.raises(ValueError,
                       match=f"tiny-granite-h keeps recurrent state.*{what}"):
        BatchScheduler(qparams, CFG, TOK, num_slots=2, max_seq=64,
                       page_size=16, **kw)

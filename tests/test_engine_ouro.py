"""tiny-ouro (three layers walked four times with one set of weights, a
cache layer a (pass, layer) pair) through the scheduler, end to end on the
CPU, on the stack the benchmark serves with: int8 weights, the paged int8
pool, the prefix store, fused decode and a chunk ladder; a pool too small
for its callers, so that pages and not rows set the batch; a parked
session; the paths that refuse a looped model at boot. A module of its
own, so that its programs are freed before the next module's
(tests/conftest.py)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import family_for, llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                            GenerateRequest, RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

from solo import Solo, generate as run

CFG = get_config("tiny-ouro")
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
# One-shot prefill of the unpadded prompt, all 12 cache layers spliced
# into a one-row int8 pool, plain decode steps (tests/solo.py).
SOLO = Solo(llama, CFG, TOK, pool="int8", max_seq=256, last_only=True)
HEAD = "ouro shared head, a few dozen bytes, "
PASSES = ['serve_loop_exit_mass_total{pass="%d"}' % t for t in range(4)]


@pytest.fixture(scope="module")
def qparams():
    """int8 weights under float32 activations (tests/test_engine_pangu.py
    says why: every sublayer's output is normed, the top logits are flat,
    and in bfloat16 the last bits pick the token)."""
    return llama.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                       dtype=jnp.float32)


@pytest.fixture(scope="module")
def engine(qparams):
    """The stack the benchmark serves with, and a pool of 16 pages of 16
    under four slots: a request of 60 + 40 tokens holds 7."""
    eng = TPUEngine(qparams, CFG, TOK, num_slots=4, max_seq=256,
                    page_size=16, num_pages=17, kv_quant=True,
                    prefix_cache=True, prefix_texts=(HEAD,),
                    decode_fuse_max=4, prefill_chunk=32)
    yield eng
    eng.stop()


def test_the_pool_the_prefix_entry_and_the_log_hold_a_layer_a_pass(engine):
    """(d) ``cache_layers == num_layers * ut_steps`` wherever a cache is
    sized."""
    sched = engine.scheduler
    assert family_for(CFG) is llama and sched._model is llama
    assert CFG.cache_layers == CFG.num_layers * CFG.ut_steps == 12
    assert sched._cache.k.shape == (12, 17, 16, 4, 32)
    assert sched._cache.k_scale.shape == (12, 17, 4, 128)
    assert sched._page_kv_layers == 12
    assert sched._page_token_bytes == 2 * 4 * (32 + 4)
    built = sched.register_prefix(HEAD)
    assert built == len(TOK.encode(HEAD, add_bos=True)) - 1
    entry = sched._prefix.snapshot()[0]
    assert entry.k.shape == entry.v.shape == (12, built, 4, 32)


def test_prefix_hit_and_cold_admission_stream_the_models_tokens(qparams,
                                                                engine):
    """(d) A prompt behind the registered head (its 12 layers of prefix K
    and V come from the entry), a cold one through a chunk ladder, a lone
    short one: each streams the solo loop's greedy tokens on the unpadded
    prompt, so a hit and a cold admission read the same logits."""
    m0 = engine.metrics_snapshot()
    for prompt in (HEAD + "x" * 50, "y" * 75, "alone"):
        assert run(engine, prompt, max_tokens=12)[0] == SOLO(qparams, prompt,
                                                            12), prompt
    m = engine.metrics_snapshot()
    assert m["serve_prefix_admits_total"] - m0["serve_prefix_admits_total"] \
        == 1
    assert m["prefill_chunks_total"] > m0["prefill_chunks_total"]


def test_pages_bind_requests_wait_and_every_one_completes(qparams, engine):
    """(e) Six callers, four slots, 16 pages: the pool admits two or three
    rows, the others wait in ``_waiting`` beside free rows, the pages a
    finished row frees admit the next waiter, every request completes and
    none fails; the counters say so."""
    eng = engine
    m0 = eng.metrics_snapshot()
    prompts = ["p" * 60, "q" * 58, "r" * 55, "s" * 50, "t" * 45,
               HEAD + "u" * 30]
    got, errs = {}, []

    def worker(p):
        try:
            got[p] = run(eng, p, max_tokens=40)[0]
        except Exception as e:   # noqa: BLE001
            errs.append((p, e))

    threads = [threading.Thread(target=worker, args=(p,)) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errs, errs
    assert got == {p: SOLO(qparams, p, 40) for p in prompts}
    m = eng.metrics_snapshot()
    d = lambda k: m[k] - m0[k]
    assert d("serve_admitted_total") == 6
    assert d("serve_page_starved_iterations_total") > 0
    assert d("serve_page_starved_iterations_total") <= d(
        "serve_loop_iterations_total")
    assert m["serve_kv_free_pages"] == m["serve_kv_total_pages"] == 16
    # The loop's own counters: four passes a decode step, the stack's
    # bytes a pass, the exit pdf summed over live rows (a pdf: the masses
    # add up to the row-steps).
    steps = (d("decode_fused_steps_total") + d("serve_decode_ticks_total")
             - d("decode_fused_ticks_total"))
    assert d("serve_loop_weight_bytes_total") == (
        4 * steps * eng.scheduler._stack_bytes)
    prefills = d("serve_loop_passes_total") - 4 * steps
    assert prefills > 0 and prefills % 4 == 0
    mass = [d(k) for k in PASSES]
    assert all(x > 0 for x in mass)
    # A live row-step adds exactly one (a row that samples a stop id
    # parks inside a fused scan, which the host's count of row-steps, an
    # upper bound, cannot see).
    assert sum(mass) == pytest.approx(round(sum(mass)), abs=1e-3)
    row_steps = d("serve_decode_row_steps_total")
    assert 0.9 * row_steps <= sum(mass) <= row_steps + 1e-3
    assert d("serve_page_kv_bytes_total") == (
        12 * 2 * 4 * 36 * d("serve_attn_context_tokens_total"))


def test_a_traced_request_carries_its_passes(qparams):
    from p2p_llm_chat_tpu.obs.trace import TraceStore
    store = TraceStore(max_traces=8)
    eng = TPUEngine(qparams, CFG, TOK, num_slots=2, max_seq=128,
                    page_size=16, kv_quant=True, decode_fuse_max=2)
    eng.set_trace_store(store)
    try:
        stats = RequestStats()
        req = GenerateRequest(prompt="trace me", trace_id="ab" * 16,
                              trace_sampled=True,
                              options=GenerateOptions(max_tokens=9))
        "".join(eng.generate_stream(req, stats))
        deadline = time.monotonic() + 10
        span = None
        while span is None and time.monotonic() < deadline:
            span = next((s for s in store.get("ab" * 16)
                         if s["name"] == "sched.decode"), None)
            time.sleep(0.02)
        assert span is not None
        meta = span["meta"]
        assert meta["passes"] == 4 * meta["steps"] > 0
    finally:
        eng.stop()


def test_a_parked_session_holds_every_cache_layer_and_wakes(qparams):
    """(d) A session parked to host RAM carries 12 layers of pages, and
    the wake's forward (``verify_step_paged``, which walks the passes)
    resumes it with the tokens the resident session streams."""
    def two_turns(park: bool):
        eng = TPUEngine(qparams, CFG, TOK, num_slots=2, max_seq=256,
                        page_size=16, kv_quant=True, kv_host_gb=1.0,
                        kv_idle_s=1e9)
        try:
            def turn(prompt, ctx=()):
                stats = RequestStats()
                req = GenerateRequest(
                    prompt=prompt, session="s", context=tuple(ctx),
                    options=GenerateOptions(max_tokens=8, temperature=0.0))
                return "".join(eng.generate_stream(req, stats)), stats
            t1, s1 = turn("hello there, how are you doing today?")
            tier = eng.scheduler._tier
            if park:
                tier.idle_s = 0.0
                deadline = time.monotonic() + 10
                while tier.counts()[1] < 1 and time.monotonic() < deadline:
                    time.sleep(0.02)
                tier.idle_s = 1e9
                assert tier.counts() == (0, 1)
                with tier._mu:
                    sess = next(iter(tier._sessions.values()))
                arrays, span = sess.host
                k = arrays[0]
                # (pages padded to a power of two for the copy program)
                assert k.shape[0] == 12 and k.shape[1] >= span > 0
            t2, _ = turn(" and one more thing?", s1.context)
            snap = eng.scheduler.metrics_snapshot()
            assert snap["kv_waked_total"] == 1
            return t1, t2
        finally:
            eng.stop()

    assert two_turns(park=True) == two_turns(park=False)


@pytest.mark.parametrize("kwargs,what", [
    (dict(spec_k=2), "speculative decoding"),
    (dict(mesh="mesh"), "a mesh"),
])
def test_paths_not_carried_through_refuse_a_looped_model_by_name(
        kwargs, what, qparams):
    """(f) At boot, before anything is compiled."""
    if kwargs.get("mesh"):
        from jax.sharding import Mesh
        kwargs = dict(mesh=Mesh(np.array(jax.devices()[:1]), ("tp",)))
    with pytest.raises(ValueError) as e:
        BatchScheduler(qparams, CFG, TOK, num_slots=2, max_seq=64,
                       **kwargs)
    msg = str(e.value)
    assert "tiny-ouro walks its 3 layers 4 times a token" in msg
    assert what in msg


@pytest.mark.parametrize("name", ["tiny-moe", "tiny-pangu",
                                  "tiny-nemotron-h"])
def test_other_families_refuse_a_looped_stack_by_name(name):
    """(f) Only models/llama.py's walk knows passes."""
    cfg = get_config(name).with_(ut_steps=2)
    with pytest.raises(ValueError, match="ut_steps.*is not served under "
                                         "the (mixtral|pangu|nemotron_h) "
                                         "family"):
        BatchScheduler({"embed": jnp.zeros((4, 4))}, cfg, TOK, num_slots=2,
                       max_seq=64)


def test_api_show_reports_the_passes_and_the_caches_depth(qparams):
    from p2p_llm_chat_tpu.serve.api import OllamaServer
    backend = type("B", (), {"name": "tiny-ouro", "config": CFG,
                             "models": lambda self: ["tiny-ouro"]})()
    api = OllamaServer.__new__(OllamaServer)
    api.backend = backend
    api._resolve = lambda name: backend
    req = type("R", (), {"json": lambda self: {"model": "tiny-ouro"}})()
    info = api._show(req).body["model_info"]
    # The dense family's keys: a looped stack is a mechanism of it, and
    # names no model.
    assert info["general.architecture"] == "llama"
    assert info["llama.block_count"] == 3
    assert info["llama.loop.pass_count"] == 4
    assert info["llama.attention.block_count"] == 12


def test_a_large_carry_is_written_in_place_by_the_last_chunk(qparams,
                                                             monkeypatch):
    """A ladder's last chunk hands a carry over ``_CARRY_IN_PLACE_BYTES``
    back, donated (at the published widths a row of 1,024 positions is 1.6
    GB and its copies did not fit the chip): with the limit at 0 every
    ladder takes that form, and streams the same tokens."""
    from p2p_llm_chat_tpu.serve import scheduler as sched_mod
    monkeypatch.setattr(sched_mod, "_CARRY_IN_PLACE_BYTES", 0)
    eng = TPUEngine(qparams, CFG, TOK, num_slots=2, max_seq=256,
                    page_size=16, kv_quant=True, prefill_chunk=32)
    try:
        prompt = "w" * 75                   # bucket 128: four chunks
        assert run(eng, prompt, max_tokens=10)[0] == SOLO(qparams, prompt,
                                                         10)
        assert eng.metrics_snapshot()["prefill_chunks_total"] >= 4
    finally:
        eng.stop()

"""An admission's five host arrays ride to the device in one packed
buffer (serve/scheduler.py ``_admit_layout`` / ``_admit_unpack``): the
layout round-trips bit for bit, every family's four admission paths
(single-shot, prefix, chunk ladder, wake) sample the tokens they sampled
before the buffer existed, a prompt is uploaded once however many chunks
it takes, and the promotion worker's ahead-of-time programs take what
the jit wrappers take.

The pinned tokens were taken on the parent commit (five separate
uploads), on this file's fixed seeds and options: every one of the five
arrays reaches the sample (temperature, top_k, top_p, a repeat penalty
that is not 1.0 over the prompt's tail, the seed), so a word that lands
at another offset changes them."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import family_for
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.serve import scheduler as sched_mod
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

FAMILIES = ("tiny", "tiny-moe", "tiny-olmoe", "tiny-pangu",
            "tiny-nemotron-h")
HEAD = "one shared head for all, "
OPTS = dict(temperature=0.8, top_k=20, top_p=0.9, repeat_penalty=1.3)
NEW = 6
# A suffix bucket of 128 at a chunk width of 32: first, mid, mid, final.
LADDER_CHUNKS = 4

PROMPTS = {
    "single": "alone and short",
    "prefix": HEAD + "and a short tail",
    "ladder": "a prompt long enough to land in the widest bucket: " + "xyz " * 15,
    "wake": "the first turn of a conversation that goes on",
}
WAKE_TURN2 = " and its second turn"
SEEDS = {"single": 7, "prefix": 9, "ladder": 7}

# (family, path) -> the tokens the parent commit sampled.
PINNED = {
    ("tiny", "single"): [362, 80, 233, 344, 336, 450],
    ("tiny", "prefix"): [205, 103, 226, 59, 433, 144],
    ("tiny", "ladder"): [302, 415, 178, 64, 24, 480],
    ("tiny", "wake"): [137, 24, 368, 308, 248, 16],
    ("tiny-moe", "single"): [204, 40, 483, 495, 79, 288],
    ("tiny-moe", "prefix"): [28, 24, 65, 444, 349, 344],
    ("tiny-moe", "ladder"): [415, 326, 122, 233, 125, 43],
    ("tiny-moe", "wake"): [21, 173, 22, 264, 235, 366],
    ("tiny-olmoe", "single"): [138, 92, 432, 456, 159, 203],
    ("tiny-olmoe", "prefix"): [340, 378, 437, 55, 368, 354],
    ("tiny-olmoe", "ladder"): [289, 76, 28, 215, 460, 59],
    ("tiny-olmoe", "wake"): [468, 400, 401, 285, 476, 447],
    ("tiny-pangu", "single"): [342, 47, 351, 382, 38, 298],
    ("tiny-pangu", "prefix"): [315, 337, 416, 242, 3, 318],
    ("tiny-pangu", "ladder"): [348, 133, 233, 358, 91, 143],
    ("tiny-pangu", "wake"): [360, 145, 461, 27, 382, 85],
    ("tiny-nemotron-h", "single"): [470, 51, 461, 480, 506, 238],
    ("tiny-nemotron-h", "prefix"): [50, 161, 123, 212, 48, 374],
    ("tiny-nemotron-h", "ladder"): [410, 474, 171, 153, 204, 90],
}


def _generate(eng, prompt, seed, session="", ctx=()):
    stats = RequestStats()
    req = GenerateRequest(prompt=prompt, session=session, context=tuple(ctx),
                          options=GenerateOptions(max_tokens=NEW, seed=seed,
                                                  **OPTS))
    for _ in eng.generate_stream(req, stats):
        pass
    return stats.context[stats.prompt_tokens:], stats


def _wait(fn, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.02)
    raise AssertionError("timed out")


def _build(family):
    """An engine on the stack the benchmark serves with (int8 weights
    under float32 activations, the int8 page pool, the prefix store, a
    chunk ladder), and session parking wherever the family allows it (a
    hybrid model refuses it: the state would stay behind)."""
    cfg = get_config(family)
    params = family_for(cfg).init_params_quantized(
        cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    tok = ByteTokenizer(vocab_size=cfg.vocab_size)
    eng = TPUEngine(params, cfg, tok, num_slots=4, max_seq=256,
                    page_size=16, kv_quant=True, prefix_cache=True,
                    prefix_texts=(HEAD,), decode_fuse_max=1,
                    prefill_chunk=32,
                    kv_host_gb=0.0 if cfg.is_hybrid else 1.0)
    assert eng.scheduler.register_prefix(HEAD) > 0
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


def run_path(eng, path):
    """The tokens ``path`` samples, and how many admission batches,
    chunks, prefix admissions and wakes it took."""
    sched = eng.scheduler

    def counts():
        return np.array([
            sched._n_admit_batches, sched._n_prefill_chunks,
            sched._n_prefix_admits,
            sched._tier.stats()["waked_total"] if sched._tier else 0,
            getattr(sched, "_n_admit_uploads", 0)])

    if path == "wake":
        held = sum(sched._tier.counts())
        _, s1 = _generate(eng, PROMPTS["wake"], seed=11, session="s")
        _wait(lambda: sum(sched._tier.counts()) == held + 1)
        before = counts()
        toks, _ = _generate(eng, WAKE_TURN2, seed=12, session="s",
                            ctx=s1.context)
    else:
        before = counts()
        toks, _ = _generate(eng, PROMPTS[path], seed=SEEDS[path])
    _wait(lambda: all(s is None for s in sched._slots))
    return toks, counts() - before


# admission batches, chunks, prefix admissions, wakes
TOOK = {"single": (1, 0, 0, 0), "prefix": (1, 0, 1, 0),
        "ladder": (1, LADDER_CHUNKS, 0, 0), "wake": (1, 0, 0, 1)}
CASES = [(f, p) for f in FAMILIES for p in TOOK
         if not (p == "wake" and get_config(f).is_hybrid)]


# What a path's admission took, kept for the cases that read it.
_TOOK: dict = {}


@pytest.mark.parametrize("family,path", CASES)
def test_admission_samples_the_parents_tokens(engines, family, path):
    toks, took = run_path(engines(family), path)
    _TOOK[family, path] = took
    assert tuple(took[:4]) == TOOK[path]
    assert toks == PINNED[family, path]


@pytest.mark.parametrize("family,path", CASES)
def test_an_admission_uploads_once_whatever_its_chunks(engines, family, path):
    """``serve_admit_uploads_total`` rises by one for a single-shot
    admission, a prefix admission and a wake, and by one for a ladder of
    four chunks: over batches + chunks, a fifth of a transfer a
    dispatch."""
    took = _TOOK.get((family, path))
    if took is None:
        _, took = run_path(engines(family), path)
    assert took[4] == 1
    assert took[0] + took[1] == (1 + LADDER_CHUNKS if path == "ladder" else 1)


# -- the layout -------------------------------------------------------------------

SHAPES = [(1, 16, 16), (8, 256, 128), (1, 2048, 32), (32, 64, 4), (3, 48, 7)]
# Bit patterns a value-preserving conversion would lose or a float
# comparison would not tell apart.
ODD_FLOATS = np.array([-0.0, 1e-45, np.inf, -np.inf, 1.3, 1.1754942e-38],
                      np.float32)


def _filled(R, S, mppr):
    rng = np.random.default_rng(R * 1000 + S)
    buf, views = sched_mod._admit_buffer(R, S, mppr, 512)
    tokens, ints, floats, rings, tables = views
    tokens[:] = rng.integers(0, 512, tokens.shape)
    ints[:] = rng.integers(-2**31, 2**31 - 1, ints.shape)
    floats[:] = rng.choice(ODD_FLOATS, floats.shape)
    floats[2, 0] = 1.3                      # a repeat penalty that is not 1.0
    rings[:] = rng.integers(0, 513, rings.shape)
    tables[:] = rng.integers(0, 4096, tables.shape)
    return buf, [np.array(v) for v in views]


@pytest.mark.parametrize("R,S,mppr", SHAPES)
def test_layout_tiles_the_buffer(R, S, mppr):
    """A row an entry; the five parts' columns tile a row with no word
    shared and none left over, and the shape names R and S."""
    cuts = sched_mod._admit_layout(mppr)
    assert cuts == (5, 8, 8 + sched_mod._RING, 8 + sched_mod._RING + mppr)
    buf, views = sched_mod._admit_buffer(R, S, mppr, 512)
    assert buf.dtype == np.int32 and buf.shape == (R, cuts[-1] + S)
    assert [v.shape for v in views] == [
        (R, S), (5, R), (3, R), (R, sched_mod._RING), (R, mppr)]
    buf[:] = 0
    for v in views:
        v.view(np.int32)[...] += 1           # floats: as their bits
    assert (buf == 1).all()
    # Entry r's values sit in row r and nowhere else.
    buf[:] = 0
    tokens, ints, floats, rings, tables = views
    for v in (tokens, rings, tables):
        v[R - 1] = 1
    for v in (ints, floats):
        v.view(np.int32)[:, R - 1] = 1
    assert not buf[: R - 1].any() and buf[R - 1].all()


@pytest.mark.parametrize("R,S,mppr", SHAPES)
def test_layout_round_trips_bit_for_bit_on_the_host(R, S, mppr):
    buf, wrote = _filled(R, S, mppr)
    read = sched_mod._admit_unpack(buf.copy(), mppr)
    for w, r in zip(wrote, read):
        assert w.dtype == r.dtype and w.shape == r.shape
        assert w.tobytes() == np.ascontiguousarray(r).tobytes()
    assert read[2].dtype == np.float32
    assert np.signbit(wrote[2]).sum() == np.signbit(read[2]).sum()


@pytest.mark.parametrize("R,S,mppr", SHAPES)
def test_layout_round_trips_bit_for_bit_through_a_program(R, S, mppr):
    buf, wrote = _filled(R, S, mppr)
    read = jax.jit(lambda b: sched_mod._admit_unpack(b, mppr))(
        jax.device_put(buf))
    for w, r in zip(wrote, read):
        r = np.asarray(r)
        assert w.dtype == r.dtype and w.shape == r.shape
        assert w.tobytes() == r.tobytes()


def test_a_fresh_buffer_holds_what_an_empty_entry_holds():
    buf, (tokens, ints, floats, rings, tables) = sched_mod._admit_buffer(
        2, 16, 4, 512)
    assert not tokens.any() and not ints.any() and not tables.any()
    assert floats.tolist() == [[0.0, 0.0], [1.0, 1.0], [1.0, 1.0]]
    assert (rings == 512).all()
    # The views write through.
    floats[2, 1] = 1.3
    assert sched_mod._admit_unpack(buf, 4)[2][2, 1] == np.float32(1.3)


# -- the promotion worker's programs --------------------------------------------

def _on_loop(sched, fn):
    """Run ``fn`` on the scheduler thread, which owns the device
    buffers, and hand back what it returned."""
    import threading
    out = []
    job = sched_mod._WarmupJob(lambda: out.append(fn()), threading.Event())
    sched._admit_q.put(job)
    assert job.done.wait(timeout=120)
    if job.err is not None:
        raise job.err
    return out[0]


@pytest.mark.parametrize("kind", ["single", "ladder"])
@pytest.mark.parametrize("family", FAMILIES)
def test_the_promotion_workers_programs_take_what_the_wrappers_take(
        engines, family, kind):
    """The ahead-of-time programs (_compile_promotion_aot, off the loop
    thread, from shapes alone) are called by the dispatch code that
    calls the jit wrappers, with its arguments: a request served
    through them samples what it sampled through the wrappers, and no
    wrapper compiles."""
    eng = engines(family)
    sched = eng.scheduler
    entry = sched._prefix.snapshot()[0]
    P, C, R = entry.length, 32, 1
    S, offs = (32, None) if kind == "single" else (128, (0, 32, 64, 96))
    prompt = (PROMPTS["prefix"] if kind == "single"
              else HEAD + "and a tail as long as a ladder: " + "uvw " * 15)
    through_wrappers, _ = _generate(eng, prompt, seed=SEEDS["prefix"])
    if kind == "single":
        assert through_wrappers == PINNED[family, "prefix"]
    _wait(lambda: all(s is None for s in sched._slots))
    structs = _on_loop(sched, sched._promotion_structs)
    aot_admit, aot_chunks = sched._compile_promotion_aot(
        P, entry.k, entry.v, entry.state, [(S, R, C, offs)], structs)
    assert set(aot_admit) == ({(P, S, R)} if offs is None else set())
    assert set(aot_chunks) == {(P, S, off, C, R) for off in offs or ()}

    def install():
        sched._admit_prefix_aot.update(aot_admit)
        sched._prefill_chunk_aot.update(aot_chunks)
        return (sched._admit_prefix_j._cache_size(),
                [sched._prefill_chunk_for(P, S, off, C)._cache_size()
                 for off in offs or ()])
    sizes = _on_loop(sched, install)
    before = (sched._n_prefix_admits, sched._n_prefill_chunks)
    through_aot, _ = _generate(eng, prompt, seed=SEEDS["prefix"])
    _wait(lambda: all(s is None for s in sched._slots))
    assert through_aot == through_wrappers
    assert sched._n_prefix_admits == before[0] + 1
    assert sched._n_prefill_chunks == before[1] + len(offs or ())
    assert _on_loop(sched, install) == sizes


def test_the_counter_is_exported_and_warmup_is_not_in_it(engines):
    eng = engines("tiny")
    sched = eng.scheduler
    before = sched.metrics_snapshot()
    eng.warmup(buckets=(32,))
    after = sched.metrics_snapshot()
    assert after["serve_boot_programs_total"] > 0
    for name in ("serve_admit_uploads_total", "serve_admit_batches_total"):
        assert after[name] == before[name]
    assert "serve_admit_uploads_total" in eng.metrics_snapshot()

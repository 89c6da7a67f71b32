"""tiny-phi4flash (the SambaY kinds of models/nemotron_h.py: Mamba-1,
window attention over a ring, one full layer whose pages the cross
layers read, gated memory units, differential attention, biased
LayerNorms) against its plain reference
(benchmark/architectures/phi4flash.py), on logits, seeded weights, on
the CPU: one piece, as a chunk ladder with a padded last chunk, and
decode through ring, page pool and state pool, over thirteen windows;
what each cache holds; every wrong model failing the limits."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, reference, serve_cell
from p2p_llm_chat_tpu.models import family_for, nemotron_h
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops import state_pool
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache

from solo import jit_model

ROOT = os.path.join(manifest.REPO, "benchmark")
CFG = get_config("tiny-phi4flash")
CHUNK = 16


def tiny_file() -> dict:
    """The published configuration file at the test size's widths."""
    with open(os.path.join(ROOT, "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        cfg = json.load(f)
    return {**cfg, "name": "tiny-phi4flash", "hidden_size": 128,
            "intermediate_size": 256, "num_attention_heads": 8,
            "num_key_value_heads": 4, "num_hidden_layers": 8,
            "sliding_window": 8, "vocab_size": 512,
            "max_position_embeddings": 256,
            "assumed": {**cfg["assumed"], "mamba_dt_rank": 8},
            "stack": {**cfg["stack"], "SERVE_PREFILL_CHUNK": str(CHUNK)}}


FILE = tiny_file()
ARCH = manifest.load_architecture(ROOT, "phi4flash")
TOKENS = jnp.asarray(np.random.default_rng(1).integers(0, 512, (2, 40)),
                     jnp.int32)


def fake_sched(params, dtype, kv_quant):
    return types.SimpleNamespace(
        _model=nemotron_h, _params=params, config=CFG, mesh=None,
        page_size=4, _dtype=dtype, kv_quant=kv_quant, prefill_chunk=CHUNK)


@pytest.fixture(scope="module")
def plain():
    """float32 everywhere: the program against the reference without
    rounding between them."""
    params = nemotron_h.init_params(CFG, jax.random.PRNGKey(0),
                                    dtype=jnp.float32)
    sched = fake_sched(params, jnp.float32, False)
    return sched, ARCH.engine_weights(sched)


@pytest.fixture(scope="module")
def served():
    """The stack the cell serves with: int8 weights under bfloat16
    activations, int8 rings and pages, float32 state."""
    params = nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(0),
                                              dtype=jnp.bfloat16)
    sched = fake_sched(params, jnp.bfloat16, True)
    weights = ARCH.engine_weights(sched)
    return ARCH.system_logits(sched, TOKENS, 32), weights


def test_the_file_builds_the_registered_test_size():
    import dataclasses
    mc = serve_cell.model_config(FILE, ROOT)
    differ = {f.name for f in dataclasses.fields(mc)
              if getattr(mc, f.name) != getattr(CFG, f.name)}
    assert differ == {"eos_token_ids"}
    assert family_for(mc) is nemotron_h
    big = get_config("phi-4-mini-flash-reasoning")
    assert big.hybrid_pattern == ARCH.pattern(json.load(open(os.path.join(
        ROOT, "configs", "phi-4-mini-flash-reasoning.json"))))
    assert (big.ssm_layers, big.window_layers, big.cache_layers) == (9, 8, 1)


def test_walk_scans_each_half_and_leaves_the_publishers_out():
    plan = nemotron_h._plan(get_config(
        "phi-4-mini-flash-reasoning").hybrid_pattern)
    assert [(letters, n) for letters, n, _ in plan] == [
        ("1-w-", 8), ("Y", 1), ("-", 1), ("*", 1), ("-g-x", 7), ("-", 1)]
    # The other hybrid's walk is what it was.
    assert [(letters, n) for letters, n, _ in nemotron_h._plan(
        "MEMEMEM*EMEMEMEM*EMEME")] == [
        ("ME", 3), ("M", 1), ("*", 1), ("EM", 4), ("*", 1), ("EM", 2),
        ("E", 1)]


def test_program_equals_reference_through_ladder_ring_pages_and_state(
        plain):
    """Both samples of the check: the harness's through one chunk, the
    long one (6 chunks of 16 and a padded seventh, 107 positions = 13
    windows of 8, then 8 decode steps) through the chunk ladder, the
    install and decode; every compared position within 1e-4."""
    sched, weights = plain
    system = ARCH.system_logits(sched, TOKENS, 32)
    ref, facts = ARCH.forward(FILE, TOKENS, weights)
    P, D = ARCH.long_shape(CHUNK)
    assert P >= 5 * CFG.sliding_window and P % CHUNK
    assert system.long_logits.shape == facts["long_logits"].shape
    assert float(jnp.max(reference.position_errors(system.logits,
                                                   ref))) < 1e-4
    assert float(jnp.max(reference.position_errors(
        system.long_logits, facts["long_logits"]))) < 1e-4
    out = ARCH.compare(system, ref, {**facts, "n_prefill": 32}, FILE)
    assert out["ok"] and out["state_error"] < 1e-5
    assert abs(out["window_edge"]) < 1e-3


def test_one_piece_prefill_equals_the_reference(plain):
    sched, weights = plain
    long = jnp.asarray(ARCH.long_tokens(TOKENS, 512, CHUNK))
    T = long.shape[1]
    cache = KVCache.create(CFG, 1, T, dtype=jnp.float32)
    logits, cache = jit_model(nemotron_h.prefill, CFG)(
        sched._params, long, jnp.asarray([T]), cache)
    ref, _ = ARCH._stack(FILE, long, weights, CFG.sliding_window)
    assert float(jnp.max(reference.position_errors(logits, ref))) < 1e-4


def test_what_each_cache_holds():
    """A window layer's ring holds ``window`` positions a row at every
    length; the page pool is one layer deep; the cross layers and the
    gated memory units allocate nothing."""
    for slots, pages in ((3, 5), (7, 40)):
        pool = PagedKVCache.create(CFG, slots, pages, 16, quantized=True)
        assert pool.k.shape == (1, pages, 16, 1, 64)
        st = pool.state
        assert st.win_k.shape == st.win_v.shape == (2, slots + 1, 1, 8, 64)
        assert st.win_k.dtype == jnp.int8
        assert st.win_ks.shape == (2, slots + 1, 1, 8)
        assert st.ssm.shape == (3, slots + 1, 16, 256)
        assert st.ssm.dtype == jnp.float32
        assert st.conv.shape == (3, slots + 1, 3, 256)
        assert len(jax.tree.leaves(pool)) == 6 + 6
    for width in (24, 200):
        small = KVCache.create(CFG, 2, width)
        assert small.k.shape == (1, 2, width, 1, 64)
        assert small.state.win_k.shape == (2, 2, 1, 8, 64)
        assert small.state.win_ks is None
    big = get_config("phi-4-mini-flash-reasoning")
    st = jax.eval_shape(lambda: PagedKVCache.create(
        big, 32, 2049, 64, quantized=True)).state
    assert st.win_k.shape == (8, 33, 1, 512, 1280)
    assert st.ssm.shape == (9, 33, 16, 5120)
    # The other hybrid's state has no ring.
    assert PagedKVCache.create(get_config("tiny-nemotron-h"), 2, 5,
                               16).state.win_k is None


def _filled_pool(B=3, seed=3):
    pool = PagedKVCache.create(CFG, B, 1 + B * 4, 16, max_pages_per_row=4,
                               dtype=jnp.float32, quantized=True)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def fill(a):
        if a.dtype == jnp.int8:
            return jax.random.randint(next(keys), a.shape, -127, 128,
                                      jnp.int8)
        return jax.random.uniform(next(keys), a.shape, a.dtype, 0.01, 1.0)

    return pool._replace(
        state=state_pool.StatePool(*(fill(a) for a in pool.state)),
        page_table=1 + jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4),
        lengths=jnp.asarray([5, 7, 19], jnp.int32))


@pytest.fixture(scope="module")
def qparams():
    return nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                            dtype=jnp.float32)


@pytest.mark.parametrize("fused", [False, True])
def test_rows_not_live_keep_ring_and_state_bit_for_bit(qparams, fused):
    pool = _filled_pool()
    before = pool.state
    active = jnp.asarray([True, False, True])
    toks = jnp.asarray([[3], [4], [5]])
    if fused:
        def sample(logits, st, emit_pos, act):
            return jnp.argmax(logits, -1).astype(jnp.int32), st

        after = jit_model(
            nemotron_h.decode_fused, CFG, active=active, num_steps=2,
            sample_fn=sample, sample_state=(),
            stop_ids=jnp.asarray([-1]), pages=4)(qparams, toks, pool)[3]
        steps = 2
    else:
        _, after = jit_model(nemotron_h.decode_step_paged, CFG,
                             active=active, pages=4)(qparams, toks, pool)
        steps = 1
    for b, a in zip(before, after.state):
        b, a = np.asarray(b), np.asarray(a)
        assert np.array_equal(b[:, 1], a[:, 1])      # the parked row
        assert not np.array_equal(b[:, 0], a[:, 0])
        assert not np.array_equal(b[:, 2], a[:, 2])
    # A live row's ring changed in the slots it wrote and nowhere else.
    wk0, wk1 = np.asarray(before.win_k), np.asarray(after.state.win_k)
    W = CFG.sliding_window
    for row, length in ((0, 5), (2, 19)):
        wrote = {(length + j) % W for j in range(steps)}
        for slot in range(W):
            same = np.array_equal(wk0[:, row, :, slot],
                                  wk1[:, row, :, slot])
            assert same == (slot not in wrote)
    assert list(np.asarray(after.lengths)) == [5 + steps, 7, 19 + steps]


def test_decode_program_aliases_pools_and_copies_neither(qparams):
    """The compiled decode step hands back the buffers it was given
    (``input_output_alias``, as tests/test_state_pool.py reads it), and
    its optimised HLO holds no ``copy`` of the shape of a ring. (The CPU
    compiler copies the float32 state around its layer loop and the page
    pool before the step's write, here as for the other hybrid; whether
    the TPU compiler does is read off the chip's own HLO by
    tools/check_pool_copies.py, which knows these shapes.)"""
    pool = _filled_pool()
    fn = jax.jit(lambda p, t, c: nemotron_h.decode_step_paged(
        p, CFG, t, c, pages=4), donate_argnums=(2,))
    text = fn.lower(qparams, jnp.asarray([[3], [4], [5]]),
                    pool).compile().as_text()
    assert "input_output_alias" in text

    def shape_of(a):
        kind = {"int8": "s8", "float32": "f32", "int32": "s32"}[a.dtype.name]
        return f"{kind}[{','.join(map(str, a.shape))}]"

    whole = {shape_of(a) for a in pool.state[2:]}
    copies = [line for line in text.splitlines()
              if " copy(" in line and any(line.split("=")[1].strip()
                                          .startswith(s) for s in whole)]
    assert not copies, copies


def test_served_precision_passes_and_every_wrong_model_fails(served):
    system, weights = served
    ref, facts = ARCH.forward(FILE, TOKENS, weights)
    sound = ARCH.compare(system, ref, {**facts, "n_prefill": 32}, FILE)
    assert sound["ok"], sound
    assert sound["median"] < 0.03 and sound["long_median"] < 0.03
    assert abs(sound["window_edge"]) < 0.05


# bf16_state is held by the state limit, which is set on the chip at the
# published widths (a 16-step-memory state at test size drifts less than
# the limit allows): here it must read clearly above the sound state.
@pytest.mark.parametrize("name", ARCH.WRONG)
def test_wrong_model_fails(served, name):
    system, weights = served
    cfg, w = ARCH.wrong_models(FILE, weights)[name]
    ref, facts = ARCH.forward(cfg, TOKENS, w)
    out = ARCH.compare(system, ref, {**facts, "n_prefill": 32}, FILE)
    if name == "bf16_state":
        sound_ref, sound_facts = ARCH.forward(FILE, TOKENS, weights)
        sound = ARCH.compare(system, sound_ref,
                             {**sound_facts, "n_prefill": 32}, FILE)
        assert out["state_error"] > 1.5 * sound["state_error"]
        return
    assert not out["ok"], (name, out)
    if name == "window_one_short":
        assert out["window_edge"] > 0.9

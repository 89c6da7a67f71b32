"""tiny-lfm2 (the LFM2 kinds of models/nemotron_h.py: a gated short
convolution that keeps a two-position window a row and no pages, GQA at a
head of 64 with per-head QK-norm over a pool that keeps its KV heads in
pairs, dense and routed feed-forwards of different widths in one pattern,
a biased-sigmoid router over SwiGLU experts) against its plain reference
(benchmark/architectures/lfm2.py), on logits, seeded weights, on the CPU:
one piece; as a chunk ladder with chunks of 1, 2, 3 and many positions and
a padded last chunk; decode and fused decode through windows and pages;
rows of different lengths in one batch; rows not live keeping their
windows bit for bit; the paired page geometry against the per-head one and
the zero-extended flash-append against both; the router against the
published rule; every wrong model failing the limit."""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, reference, serve_cell
from p2p_llm_chat_tpu.models import family_for, nemotron_h, pangu
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops import paged_attention as pa
from p2p_llm_chat_tpu.ops import state_pool
from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache, write_prefill_batch,
                                           write_prefill_row)
from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler

from solo import jit_model

ROOT = os.path.join(manifest.REPO, "benchmark")
NAME = "lfm2-8b-a1b"
CFG = get_config("tiny-lfm2")
CHUNK = 16
prefill = jit_model(nemotron_h.prefill, CFG)


def published() -> dict:
    with open(os.path.join(ROOT, "configs", NAME + ".json")) as f:
        return json.load(f)


def tiny_file(chunk: int = CHUNK) -> dict:
    """The published configuration file at the test size's widths."""
    cfg = published()
    kinds = {"c": "conv", "*": "full_attention"}
    return {**cfg, "name": "tiny-lfm2", "hidden_size": 128,
            "intermediate_size": 192, "moe_intermediate_size": 64,
            "num_attention_heads": 8, "num_key_value_heads": 4,
            "head_dim": 64, "num_hidden_layers": 15,
            "layer_types": [kinds[ch] for ch in CFG.hybrid_pattern[0::2]],
            "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
            "max_position_embeddings": 256, "rope_theta": 10000.0,
            "stack": {**cfg["stack"], "SERVE_PREFILL_CHUNK": str(chunk)}}


FILE = tiny_file()
ARCH = manifest.load_architecture(ROOT, "lfm2")
TOKENS = jnp.asarray(np.random.default_rng(1).integers(0, 512, (2, 40)),
                     jnp.int32)


@pytest.fixture(autouse=True)
def short_long_sample(monkeypatch):
    """The long sample at test size: 40 positions and more, not 3,500."""
    monkeypatch.setattr(ARCH, "LONG_MIN", 40)


def fake_sched(params, dtype, kv_quant, chunk: int = CHUNK):
    return types.SimpleNamespace(
        _model=nemotron_h, _params=params, config=CFG, mesh=None,
        page_size=4, _dtype=dtype, kv_quant=kv_quant, prefill_chunk=chunk,
        num_slots=5, decode_fuse_max=3)


@pytest.fixture(scope="module")
def plain():
    """float32 everywhere: the program against the reference without
    rounding between them."""
    params = nemotron_h.init_params(CFG, jax.random.PRNGKey(0),
                                    dtype=jnp.float32)
    sched = fake_sched(params, jnp.float32, False)
    return sched, ARCH.engine_weights(sched)


def _served(dtype):
    params = nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(0),
                                              dtype=dtype)
    sched = fake_sched(params, dtype, True)
    return sched, ARCH.engine_weights(sched)


@pytest.fixture(scope="module")
def served():
    """int8 weights and pages under float32 activations and windows."""
    return _served(jnp.float32)


def test_the_file_builds_the_registered_test_size_and_the_published_one():
    mc = serve_cell.model_config(FILE, ROOT)
    differ = {f.name for f in dataclasses.fields(mc)
              if getattr(mc, f.name) != getattr(CFG, f.name)}
    assert differ == {"eos_token_ids"}          # ignore_eos
    big = serve_cell.model_config(published(), ROOT)
    reg = get_config(NAME)
    assert {f.name for f in dataclasses.fields(big)
            if getattr(big, f.name) != getattr(reg, f.name)} == {
                "eos_token_ids"}
    assert family_for(big) is nemotron_h
    assert big.hybrid_pattern == ("c-c-*E" + "cEcEcE*E" * 4 + "cEcE*E"
                                  + "cEcE")
    assert (big.ssm_layers, big.short_conv_layers, big.conv_layers,
            big.window_layers, big.state_layers, big.cache_layers,
            big.routed_layers) == (0, 18, 18, 0, 18, 6, 22)
    assert big.kv_paired
    assert (big.cache_kv_heads, big.cache_k_dim, big.cache_v_dim) == (
        4, 128, 128)
    assert big.state_kinds == "convolution windows (18 layers)"
    assert (big.conv_kernel, big.conv_dim) == (3, 2048)
    # The seven older configurations keep the geometry they had.
    for name in ("tiny-mellum2", "tiny-nemotron-h", "tiny-phi4flash",
                 "llama3.2-1b", "tiny"):
        assert not get_config(name).kv_paired
        assert get_config(name).conv_layers == get_config(name).ssm_layers


def test_walk_scans_the_equal_stretches_between_a_head_and_a_tail():
    """Cut before every ``*``; neighbours that differ only in how often a
    group of two letters repeats are ONE scan, the group a loop of as many
    turns as the round has copies: four periods of ``*EcEcEcE`` behind the
    two dense layers and the tail's two of ``*EcEcE`` are six rounds of
    ``*`` + ``Ec`` x m + ``E``, and a program holds one attention body and
    two routed ones. Equal neighbours are one scan as they were, whole
    periods one stretch, and a pattern whose ``*`` stretches are not alike
    (Nemotron's cut) is walked as it stands."""
    big = get_config(NAME).hybrid_pattern
    assert nemotron_h._segments(big) == (
        ("c-c-", 1), (("*", "Ec", "E"), (3, 3, 3, 3, 2, 2)))
    assert nemotron_h._segments(CFG.hybrid_pattern) == (
        ("c-c-", 1), (("*", "Ec", "E"), (2, 2, 2, 1, 1)))
    assert nemotron_h._segments("*EcEcE*EcEcE") == (("*EcEcE", 2),)
    # A stretch of another make between two runs ends the first.
    assert nemotron_h._segments("c-*EcEcE*EcE*EwE*EcEcEcE*EcE") == (
        ("c-", 1), (("*", "Ec", "E"), (2, 1)), ("*EwE", 1),
        (("*", "Ec", "E"), (3, 1)))
    assert nemotron_h._varied("*EMEMEMEM", "*EMEME") is None
    assert nemotron_h._varied("cEcE", "cEcEcE") is None
    assert nemotron_h._segments("wEwEwE*E" * 4) == (("wEwEwE*E", 4),)
    for other in ("MEMEMEM*EMEMEMEM*EMEME", "MEMEM*EMEME", "MEMEMEME",
                  get_config("tiny-phi4flash").hybrid_pattern):
        assert nemotron_h._segments(other) == ((other, 1),)
    # Unequal stretches between equal ones are walked together.
    assert nemotron_h._segments("c-*EcE*EwE*EcE*EcE") == (
        ("c-*EcE*EwE", 1), ("*EcE", 2))
    assert [(l, n) for l, n, _ in nemotron_h._plan("*EcEcEcE")] == [
        ("*", 1), ("Ec", 3), ("E", 1)]
    assert [(l, n) for l, n, _ in nemotron_h._plan("c-c-")] == [("c-", 2)]


def test_the_family_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="share the state pool's conv rows"):
        nemotron_h.init_params(dataclasses.replace(
            get_config("tiny-nemotron-h"), hybrid_pattern="McE*"),
            jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="routed layer"):
        nemotron_h.init_params(dataclasses.replace(
            CFG, moe_scoring="softmax"), jax.random.PRNGKey(0))


# -- against the reference ----------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 2, 3, 16])
def test_program_equals_reference_through_ladder_windows_and_pages(plain,
                                                                   chunk):
    """Both samples of the check with a chunk shorter than the window of
    two, equal to it, one longer and many: the harness's through one
    chunk of 32 and 8 decode steps, the long one (whole chunks and a
    padded last one where 11/16 of a chunk is a position at all, then 8
    decode steps) through the chunk ladder, the install and decode; every
    compared position within 1e-4 (float32 on both sides)."""
    sched, weights = plain
    sched = types.SimpleNamespace(**{**vars(sched), "prefill_chunk": chunk})
    file = tiny_file(chunk)
    system = ARCH.system_logits(sched, TOKENS, 32)
    ref, facts = ARCH.forward(file, TOKENS, weights)
    P, D = ARCH.long_shape(chunk)
    assert P >= 40 and D == 8 and (chunk < 3 or P % chunk)
    assert system.long_logits.shape == facts["long_logits"].shape
    assert float(jnp.max(reference.position_errors(system.logits,
                                                   ref))) < 1e-4
    assert float(jnp.max(reference.position_errors(
        system.long_logits, facts["long_logits"]))) < 1e-4
    out = ARCH.compare(system, ref, {**facts, "n_prefill": 32}, file)
    assert out["ok"], out


def test_one_piece_prefill_equals_the_reference(plain):
    sched, weights = plain
    long = jnp.asarray(ARCH.long_tokens(TOKENS, 512, CHUNK))
    T = long.shape[1]
    cache = KVCache.create(CFG, 1, T, dtype=jnp.float32)
    logits, cache = prefill(sched._params, long, jnp.asarray([T]), cache)
    ref, _ = ARCH._stack(FILE, long, weights)
    assert float(jnp.max(reference.position_errors(logits, ref))) < 1e-4
    # What the carry holds: z = B * x of the last two positions a conv
    # layer, and K and V with the KV heads in pairs.
    assert cache.state.conv.shape == (10, 1, 2, 128)
    assert cache.state.ssm.size == 0 and cache.state.win_k is None
    assert cache.k.shape == (5, 1, T, 2, 128)


def _decode_from(params, tokens, lens, steps, fused: bool, active=None):
    """Prefill ``tokens`` [B, S] (row b real to ``lens[b]``) in one batch,
    install windows and pages, then feed ``steps`` [B, n] one at a time
    or in one fused call whose sampler hands them back. Returns the
    prefill's last logits, the decode steps' and the pool."""
    B, S = tokens.shape
    lens = jnp.asarray(lens, jnp.int32)
    small = KVCache.create(CFG, B, S, dtype=jnp.float32)
    last, small = jit_model(nemotron_h.prefill, CFG, last_only=True)(
        params, tokens, lens, small)
    pool = PagedKVCache.create(CFG, B, 1 + B * 16, 4, max_pages_per_row=16,
                               dtype=jnp.float32, quantized=False)
    pool = write_prefill_batch(
        pool, small.k, small.v, jnp.arange(B), lens,
        1 + jnp.arange(B * 16, dtype=jnp.int32).reshape(B, 16))
    pool = pool._replace(state=state_pool.write_rows(
        pool.state, small.state, jnp.arange(B)))
    n = steps.shape[1]
    if not fused:
        out = []
        step = jit_model(nemotron_h.decode_step_paged, CFG, pages=16)
        for t in range(n):
            lg, pool = step(params, steps[:, t: t + 1], pool)
            out.append(lg)
        return last, jnp.concatenate(out, axis=1), pool

    script = jnp.concatenate([steps, steps[:, :1]], axis=1)

    def sample(logits, st, emit_pos, act):
        i, kept = st
        return (jnp.take(script, i + 1, axis=1),
                (i + 1, kept.at[:, i].set(logits)))

    res = jit_model(
        nemotron_h.decode_fused, CFG, num_steps=n, sample_fn=sample,
        sample_state=(jnp.zeros((), jnp.int32),
                      jnp.zeros((B, n, CFG.vocab_size), jnp.float32)),
        stop_ids=jnp.asarray([-1]), pages=16)(params, steps[:, :1], pool)
    return last, res[5][1], res[3]


@pytest.mark.parametrize("fused", [False, True])
def test_rows_of_different_lengths_in_one_batch(plain, fused):
    """Rows of 1, 2 and 29 positions (shorter than the window, equal to
    it, far past it) in one padded prefill batch, then 6 decode steps of
    all in one batch, plain and fused: each row's logits are the
    reference's on that row's own tokens."""
    sched, weights = plain
    rng = np.random.default_rng(7)
    lens = [1, 2, 29]
    seqs = [rng.integers(0, 512, n + 6).astype(np.int32) for n in lens]
    tokens = np.zeros((3, 32), np.int32)
    for b, (n, s) in enumerate(zip(lens, seqs)):
        tokens[b, :n] = s[:n]
    steps = jnp.asarray(np.stack([s[n: n + 6] for n, s in zip(lens, seqs)]))
    last, got, pool = _decode_from(sched._params, jnp.asarray(tokens), lens,
                                   steps, fused)
    assert list(np.asarray(pool.lengths)) == [7, 8, 35]
    for b, (n, s) in enumerate(zip(lens, seqs)):
        ref, _ = ARCH._stack(FILE, jnp.asarray(s[None]), weights)
        want = ref[0, n - 1: n + 6]
        have = jnp.concatenate([last[b], got[b]], axis=0)
        assert float(jnp.max(reference.position_errors(have, want))) < 1e-4


def test_a_padded_row_leaves_its_window_as_its_unpadded_run_would(plain):
    """Padding never enters the window: what a padded chunk leaves is the
    window of the same positions prefilled without padding; and a chunk of
    one position behind a carried window keeps the older of the two."""
    sched, _ = plain
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 512, (1, 21)),
                      jnp.int32)
    a = KVCache.create(CFG, 1, 21, dtype=jnp.float32)
    _, a = prefill(sched._params, ids, jnp.asarray([21]), a)
    b = KVCache.create(CFG, 1, 32, dtype=jnp.float32)
    _, b = prefill(sched._params, jnp.pad(ids, ((0, 0), (0, 11))),
                   jnp.asarray([21]), b)
    np.testing.assert_allclose(np.asarray(a.state.conv),
                               np.asarray(b.state.conv), atol=1e-5)
    w = jnp.arange(8.0).reshape(1, 2, 4)
    out, win = state_pool.conv_scan(jnp.full((1, 1, 4), 9.0), w,
                                    jnp.asarray([1]), jnp.ones((3, 4)), None)
    np.testing.assert_array_equal(np.asarray(win[0, 0]), np.asarray(w[0, 1]))
    np.testing.assert_array_equal(np.asarray(win[0, 1]), np.full(4, 9.0))
    np.testing.assert_array_equal(np.asarray(out[0, 0]),
                                  np.asarray(w[0, 0] + w[0, 1] + 9.0))
    _, kept = state_pool.conv_scan(jnp.full((1, 1, 4), 9.0), w,
                                   jnp.asarray([0]), jnp.ones((3, 4)), None)
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(w))


def test_what_each_cache_holds():
    """A conv layer keeps two positions of 2,048 a row and nothing
    recurrent; the page pool is as deep as there are attention layers and
    keeps its KV heads in pairs; there are no rings."""
    for slots, pages in ((3, 5), (7, 40)):
        pool = PagedKVCache.create(CFG, slots, pages, 16, quantized=True)
        assert pool.k.shape == pool.v.shape == (5, pages, 16, 2, 128)
        assert pool.k.dtype == jnp.int8
        st = pool.state
        assert st.conv.shape == (10, slots + 1, 2, 128)
        assert st.ssm.size == 0 and st.ssm.shape[:2] == (0, slots + 1)
        assert st.win_k is None and st.ring_nbytes == 0
        assert st.rows == slots + 1
        assert st.row_bytes == 10 * 2 * 128 * st.conv.dtype.itemsize
    big = get_config(NAME)
    pool = jax.eval_shape(lambda: PagedKVCache.create(
        big, 32, 8193, 64, quantized=True))
    assert pool.k.shape == (6, 8193, 64, 4, 128)
    assert pool.state.conv.shape == (18, 33, 2, 2048)
    assert pool.state.conv.dtype == jnp.bfloat16
    row = 18 * 2 * 2048 * 2
    assert row == ARCH.window_row_bytes(published()) == 147_456
    assert 33 * row == 4_866_048                    # 5 MB for 33 rows


def _filled_pool(B=3, seed=3):
    pool = PagedKVCache.create(CFG, B, 1 + B * 4, 16, max_pages_per_row=4,
                               dtype=jnp.float32, quantized=True)
    conv = jax.random.uniform(jax.random.PRNGKey(seed),
                              pool.state.conv.shape, jnp.float32, 0.01, 1.0)
    return pool._replace(
        state=pool.state._replace(conv=conv),
        page_table=1 + jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4),
        lengths=jnp.asarray([5, 7, 19], jnp.int32))


@pytest.fixture(scope="module")
def qparams():
    return nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                            dtype=jnp.float32)


@pytest.mark.parametrize("fused", [False, True])
def test_rows_not_live_keep_their_windows_bit_for_bit(qparams, fused):
    pool = _filled_pool()
    before = np.asarray(pool.state.conv)
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
    got = np.asarray(after.state.conv)
    assert np.array_equal(before[:, 1], got[:, 1])      # the parked row
    assert np.array_equal(before[:, 3], got[:, 3])      # the garbage row
    for row in (0, 2):
        assert not np.array_equal(before[:, row], got[:, row])
    # One step shifts a live row's window by one position.
    if steps == 1:
        assert np.array_equal(before[:, 0, 1], got[:, 0, 0])
    assert list(np.asarray(after.lengths)) == [5 + steps, 7, 19 + steps]


def test_routed_layer_is_dropless_and_counts_its_pairs(qparams):
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 512, (2, 24)),
                      jnp.int32)
    lens = jnp.asarray([24, 9])
    valid = jnp.arange(24)[None, :] < lens[:, None]
    small = KVCache.create(CFG, 2, 24, dtype=jnp.float32)
    _, _, stats = jit_model(nemotron_h.prefill_counted, CFG)(
        qparams, ids, lens, small, valid)
    pairs = 33 * CFG.num_experts_per_tok * CFG.routed_layers
    assert CFG.routed_layers == 13
    assert [int(stats[0]), int(stats[1]), int(stats[2])] == [pairs, 0, pairs]
    _, _, st = jit_model(
        nemotron_h.decode_step_paged_touched, CFG,
        active=jnp.asarray([True, False, True]), pages=4)(
            qparams, jnp.asarray([[3], [4], [5]]), _filled_pool())
    assert int(st[1]) == CFG.num_experts * CFG.routed_layers
    assert int(st[2]) == int(st[3]) == 2 * 2 * CFG.routed_layers


# -- the router ---------------------------------------------------------------

def test_routing_is_the_published_rule_where_the_bias_changes_the_choice():
    """Scores 0.9, 0.8, 0.6, 0.5 and four of 0.1: without a bias the
    first two are kept. A bias of +0.35 on the fourth lifts it over the
    second (0.85 > 0.8): the choice is {0, 3}, and the weights are the
    UNBIASED 0.9 and 0.5 over (1.4 + 1e-6), not 0.9 and 0.85."""
    scores = jnp.asarray([[0.9, 0.8, 0.6, 0.5, 0.1, 0.1, 0.1, 0.1]])
    logits = jnp.log(scores / (1 - scores))
    x = jnp.eye(8, CFG.hidden_size)[:1] * 1.0
    router = jnp.zeros((CFG.hidden_size, 8)).at[0].set(logits[0])
    bias = jnp.zeros((8,)).at[3].set(0.35)
    w, i = pangu.route(x, router, CFG, bias)
    assert sorted(np.asarray(i[0]).tolist()) == [0, 3]
    got = dict(zip(np.asarray(i[0]).tolist(), np.asarray(w[0]).tolist()))
    assert got[0] == pytest.approx(0.9 / (1.4 + 1e-6), rel=1e-6)
    assert got[3] == pytest.approx(0.5 / (1.4 + 1e-6), rel=1e-6)
    _, plain_i = pangu.route(x, router, CFG, jnp.zeros((8,)))
    assert sorted(np.asarray(plain_i[0]).tolist()) == [0, 1]
    # The reference's rule, written apart, says the same.
    weights, kept, _, _ = ARCH.route(x, router, bias, 2, 1.0)
    assert sorted(np.asarray(kept[0]).tolist()) == [0, 3]
    np.testing.assert_allclose(
        np.asarray(weights[0, [0, 3]]), [got[0], got[3]], rtol=1e-6)
    # The epsilon is the model's: 1e-6 here, 1e-20 for the other families
    # (a sum of 1e-6 tells them apart).
    small = jnp.full((1, 2), 5e-7)
    lfm = small / (jnp.sum(small, -1, keepdims=True) + CFG.moe_renorm_eps)
    assert float(lfm[0, 0]) == pytest.approx(0.25)
    assert get_config("tiny-nemotron-h").moe_renorm_eps == 1e-20
    assert get_config("tiny-mellum2").moe_renorm_eps == 1e-20


# -- the page pool at a head of 64 --------------------------------------------

def _pools(quantized: bool, seed: int = 5):
    """The same K and V of 4 KV heads x 64 in a per-head pool and in the
    paired one (2 rows x 128), rows of 9, 30 and 0 positions."""
    per_head = dataclasses.replace(get_config("tiny"), num_layers=2,
                                   num_heads=8, num_kv_heads=4, head_dim=64)
    paired = dataclasses.replace(per_head, num_heads=4, num_kv_heads=2,
                                 head_dim=128)
    key = jax.random.PRNGKey(seed)
    k = jax.random.normal(key, (2, 32, 4, 64), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape, jnp.float32)
    lens = [9, 30, 0]
    out = []
    for cfg, shape in ((per_head, (2, 32, 4, 64)), (paired, (2, 32, 2, 128))):
        pool = PagedKVCache.create(cfg, 3, 1 + 3 * 8, 4, max_pages_per_row=8,
                                   dtype=jnp.float32, quantized=quantized)
        for b, n in enumerate(lens):
            pool = write_prefill_row(
                pool, k.reshape(shape), v.reshape(shape), jnp.asarray(b),
                jnp.asarray(n), 1 + b * 8 + jnp.arange(8, dtype=jnp.int32))
        out.append(pool)
    q = jax.random.normal(jax.random.fold_in(key, 2), (3, 8, 64))
    kc = jax.random.normal(jax.random.fold_in(key, 3), (3, 4, 64))
    vc = jax.random.normal(jax.random.fold_in(key, 4), (3, 4, 64))
    return out, (q, kc, vc), jnp.asarray(lens, jnp.int32)


def test_paired_geometry_equals_the_per_head_one_on_the_gather_path():
    """A float pool: the paired gather is the per-head gather to the
    order of the sums (a query's zeros meet the other head's keys)."""
    (per_head, paired), (q, kc, vc), lens = _pools(False)
    for layer in (0, 1):
        want = pa.paged_attention_append(q, kc, vc, per_head, lens, layer,
                                         pages=8)
        got = pa.paged_attention_append_paired(q, kc, vc, paired, lens,
                                               layer, pages=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)


@pytest.mark.parametrize("quantized", [False, True])
def test_zero_extended_flash_append_equals_the_gather_path(quantized,
                                                           monkeypatch):
    """The kernel in interpret mode over the paired pool, its chunk
    budget shrunk so that a row walks several chunks: the zero-extended
    queries' outputs, their own halves taken, are the gather path's on
    the same pool (an int8 pool's scale is one a PAIR on both)."""
    monkeypatch.setattr(pa, "_FLASH_CHUNK_TOK_BYTES", 8)
    (_, paired), (q, kc, vc), lens = _pools(quantized)
    B, Hkv = 3, 4
    rep = q.shape[1] // Hkv
    args = (pa.pair_queries(q, rep), kc.reshape(B, 2, 128),
            vc.reshape(B, 2, 128), paired.k, paired.v, paired.k_scale,
            paired.v_scale, paired.page_table, lens, 1)
    flash = pa.unpair_outputs(pa._paged_attention_flash_append(
        *args, pages=8, quantized=quantized, interpret=True,
        scale=64 ** -0.5), rep)
    want = pa.paged_attention_append_paired(q, kc, vc, paired, lens, 1,
                                            pages=8)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(want),
                               atol=2e-5)
    # The pairing itself: a query lands on its own head's half and comes
    # back from it.
    ext = np.asarray(pa.pair_queries(q, rep))
    for j in range(8):
        own = slice(64, 128) if (j // rep) % 2 else slice(0, 64)
        other = slice(0, 64) if (j // rep) % 2 else slice(64, 128)
        np.testing.assert_array_equal(ext[:, j, own], np.asarray(q[:, j]))
        assert not ext[:, j, other].any()
    back = pa.unpair_outputs(jnp.asarray(ext), rep)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(q))


def test_the_rule_that_picks_the_decode_attention_sees_the_pairs_row(
        monkeypatch):
    """The flash-append guard reads the pool's row, four pairs x 128:
    neither refusal of the kernel (a head under 128 lanes, an int8 pool
    of fewer than 4 rows) meets the published pool, so on a chip its
    decode takes the kernel from the window every geometry does (256
    since PR 56; 1,024 at this width before)."""
    big = get_config(NAME)
    assert big.cache_k_dim % 128 == 0 and big.cache_kv_heads % 4 == 0
    row = BatchScheduler._flash_pool_row(big, True)
    assert row == (128, 4)
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    assert pa.effective_flash_min_w(False, *row) == 256
    assert BatchScheduler._flash_min_w(big, None, True) == 256


# -- the served precision, and the wrong models --------------------------------

def test_the_routers_products_are_float32_at_highest_in_the_program():
    """What holds the router's precision: the lowered program, not the
    check on the chip (a bfloat16 router's flips drown in those the bf16
    hidden state brings: ``router_bf16`` below). One product, float32 on
    both sides, at HIGHEST (the TPU's default is a single bf16 pass)."""
    text = jax.jit(lambda x, r, b: pangu.route(x, r, CFG, b)).lower(
        jax.ShapeDtypeStruct((4, CFG.hidden_size), jnp.bfloat16),
        jax.ShapeDtypeStruct((CFG.hidden_size, CFG.num_experts),
                             jnp.float32),
        jax.ShapeDtypeStruct((CFG.num_experts,), jnp.float32)).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == 1, dots
    assert "HIGHEST" in dots[0] and "bf16" not in dots[0], dots[0]
    assert "xf32>, tensor<%dx%dxf32>" % (CFG.hidden_size,
                                         CFG.num_experts) in dots[0]


def test_programs_hand_out_the_choices_the_reference_makes(plain):
    """``chosen``: a chunk and a decode step hand out the experts every
    routed layer kept, and in float32 they are the reference's own."""
    sched, weights = plain
    system = ARCH.system_logits(sched, TOKENS, 32)
    n_prefill, chosen, long_chosen = ARCH._CHOSEN[ARCH._digest(TOKENS)]
    assert n_prefill == 32
    assert chosen.shape == (CFG.routed_layers, TOKENS.size, 2)
    _, facts = ARCH.forward(FILE, TOKENS, weights)
    assert facts["replayed"]
    assert not bool(facts["flipped"].any() | facts["long_flipped"].any())
    assert facts["long_flipped"].shape == (CFG.routed_layers,
                                           sum(ARCH.long_shape(CHUNK)))
    # Other tokens: nothing to replay, and the verdict says so.
    other = TOKENS + 1
    ref, facts = ARCH.forward(FILE, other, weights)
    assert not facts["replayed"]
    assert not ARCH.compare(system, ref, {**facts, "n_prefill": 32},
                            FILE)["ok"]


def test_every_program_of_the_check_is_compiled_ahead(plain, monkeypatch):
    """Both sides of the check hand their programs to ``_Ahead`` before
    the first is called (a cold run's set-up is 58 s of the check where
    132 were, and a cold run that is cut leaves its server behind: PERF.md
    section 6, PR 45). A call that finds nothing compiled for its
    arguments compiles as it is called, one program after another: no
    call of the sound model does, replayed or not."""
    sched, weights = plain
    missed = []
    call = ARCH._Ahead.call

    def counted(self, name, fn, *args, **statics):
        if self._key(name, args, statics) not in self._compiled:
            missed.append(name)
        return call(self, name, fn, *args, **statics)

    monkeypatch.setattr(ARCH._Ahead, "call", counted)
    ARCH.system_logits(sched, TOKENS, 32)
    ARCH.forward(FILE, TOKENS, weights)
    ARCH.forward(FILE, TOKENS + 1, weights)          # nothing to replay
    assert not missed
    # A wrong model's variants are not foreseen, and are computed all the
    # same.
    ARCH.forward({**FILE, "_wrong": "taps_reversed"}, TOKENS, weights)
    assert set(missed) == {"op_layer"}


@pytest.fixture(scope="module")
def served_bf16():
    """The served precision whole: int8 weights and pages, bfloat16
    activations and windows; the system's logits and what it left to
    replay (the module's other tests drive other systems on the same
    tokens)."""
    sched, weights = _served(jnp.bfloat16)
    # A module's fixture is built before ``short_long_sample`` applies.
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ARCH, "LONG_MIN", 40)
        system = ARCH.system_logits(sched, TOKENS, 32)
    return weights, system, dict(ARCH._CHOSEN)


def _verdict(served_bf16, name: str = "", replay: bool = True) -> dict:
    weights, system, left = served_bf16
    ARCH._CHOSEN.clear()
    if replay:
        ARCH._CHOSEN.update(left)
    cfg, w = ARCH.wrong_models(FILE, weights)[name] if name else (FILE,
                                                                  weights)
    ref, facts = ARCH.forward(cfg, TOKENS, w)
    return ARCH.compare(system, ref, {**facts, "n_prefill": 32}, cfg)


def test_served_precision_passes_and_the_replay_is_why(served_bf16):
    """bfloat16 activations flip near ties all through a sequence: with
    the rule's own choices the worst position is ten times the replay's,
    and with the system's replayed every position is within rounding."""
    sound = _verdict(served_bf16)
    assert sound["ok"] and sound["replayed"], sound
    assert sound["max"] < 0.06 and sound["long_max"] < 0.06
    assert 0 < sound["flips"] < 0.05
    assert sound["tolerance"] == {
        "median": ARCH.TOL_MEDIAN, "long_median": ARCH.TOL_MEDIAN,
        "max": ARCH.TOL_MAX, "long_max": ARCH.TOL_MAX,
        "flips": ARCH.TOL_FLIPS}
    free = _verdict(served_bf16, replay=False)
    assert not free["ok"] and not free["replayed"]
    assert max(free["max"], free["long_max"]) > 5 * sound["long_max"]


# Which limit fails each wrong model at test size, bfloat16 (the chip's
# readings at the published widths are in the architecture file).
FAILS = {"no_expert_bias": "flips", "biased_scores_as_weights": "median",
         "taps_reversed": "median", "window_of_one": "median",
         "window_of_three": "median", "c_gate_left_out": "median",
         "qk_norm_left_out": "median",
         "qk_norm_whole_projection": "median", "carry_dropped": "long_max",
         "decode_window_stale": "max", "int4_weights": "median"}


@pytest.mark.parametrize("name", ARCH.WRONG)
def test_wrong_model_comes_out_as_not_correct(served_bf16, name):
    """Every wrong model fails the verdict, by the limit named above (and
    often by others). A missing bias leaves the logits alone under the
    replay and is a router's fault, so ``flips`` holds it; a window that
    decode never writes and one dropped between chunks leave the median
    alone and are held by the worst position, which ``decode_max`` and
    ``starts_max`` place. NOT failed, here as on the chip: ``router_bf16``, the
    router's products in bfloat16: its flips (3.0%) are the sound
    program's (2.9%), because the hidden state the system's router reads
    is bfloat16 already; the lowered program holds that precision
    (test_the_routers_products_are_float32_at_highest_in_the_program)."""
    out = _verdict(served_bf16, name)
    if name == "router_bf16":
        sound = _verdict(served_bf16)
        assert out["ok"] and out["flips"] < 1.5 * sound["flips"], out
        return
    assert not out["ok"], out
    assert out[FAILS[name]] > out["tolerance"][FAILS[name]], out
    if name == "carry_dropped":
        assert out["starts_max"] == out["long_max"] > 1
    if name == "decode_window_stale":
        assert out["decode_max"] >= out["max"] > 1 > 10 * out["starts_max"]

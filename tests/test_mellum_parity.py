"""tiny-mellum2 (the Mellum 2 kinds of models/nemotron_h.py: plain GQA over
a ring in the window layers and over pages in the full ones, two rotary
tables in one model, a routed layer of thin experts on the hidden state)
against its plain reference (benchmark/architectures/mellum.py), on
logits, seeded weights, on the CPU: one piece; as a chunk ladder with a
chunk smaller than, equal to and larger than the window and a padded last
chunk; decode and fused decode through rings and pages, the rings wrapped
several times; rows on both sides of the window in one batch; the YaRN
table against closed-form values; what each cache holds; every wrong
model failing the limits."""

import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, reference, serve_cell
from p2p_llm_chat_tpu.models import (family_for, layers, moe_tiles,
                                     nemotron_h)
from p2p_llm_chat_tpu.models.configs import (ModelConfig, RopeScaling,
                                             get_config)
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops import state_pool
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache, write_prefill_batch

from solo import jit_model

ROOT = os.path.join(manifest.REPO, "benchmark")
NAME = "mellum2-12b-a2.5b-instruct-l16"
CFG = get_config("tiny-mellum2")
W = CFG.sliding_window
CHUNK = 16
# The program's entry points, each lowered whole (tests/solo.py).
prefill = jit_model(nemotron_h.prefill, CFG)


def published() -> dict:
    with open(os.path.join(ROOT, "configs", NAME + ".json")) as f:
        return json.load(f)


def tiny_file(chunk: int = CHUNK) -> dict:
    """The published configuration file at the test size's widths."""
    cfg = published()
    return {**cfg, "name": "tiny-mellum2", "hidden_size": 64,
            "moe_intermediate_size": 32, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "num_hidden_layers": 8, "layer_types": cfg["layer_types"][:8],
            "mlp_layer_types": cfg["mlp_layer_types"][:8],
            "num_experts": 8, "num_experts_per_tok": 2, "sliding_window": 8,
            "vocab_size": 512, "max_position_embeddings": 256,
            "rope_parameters": {
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                    "original_max_position_embeddings": 16,
                    "beta_fast": 2, "beta_slow": 0.02},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 10000}},
            "stack": {**cfg["stack"], "SERVE_PREFILL_CHUNK": str(chunk)}}


FILE = tiny_file()
ARCH = manifest.load_architecture(ROOT, "mellum")
TOKENS = jnp.asarray(np.random.default_rng(1).integers(0, 512, (2, 40)),
                     jnp.int32)


def fake_sched(params, dtype, kv_quant, chunk: int = CHUNK):
    return types.SimpleNamespace(
        _model=nemotron_h, _params=params, config=CFG, mesh=None,
        page_size=4, _dtype=dtype, kv_quant=kv_quant, prefill_chunk=chunk)


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
    return (ARCH.system_logits(sched, TOKENS, 32),
            ARCH.engine_weights(sched))


@pytest.fixture(scope="module")
def served():
    """int8 weights, int8 rings and pages, under float32 activations. At
    test size a token keeps TWO experts of 8, so one flip under
    bfloat16's rounding takes half its routed output and every later
    position of a 35-token sequence with it; at the published size a flip
    is one of eight kept, and the limits are read on the chip (PERF.md
    section 6, PR 40)."""
    return _served(jnp.float32)


def test_the_file_builds_the_registered_test_size():
    import dataclasses
    mc = serve_cell.model_config(FILE, ROOT)
    differ = {f.name for f in dataclasses.fields(mc)
              if getattr(mc, f.name) != getattr(CFG, f.name)}
    assert differ == {"eos_token_ids"}
    assert family_for(mc) is nemotron_h
    big = serve_cell.model_config(published(), ROOT)
    assert family_for(big) is nemotron_h
    assert big.hybrid_pattern == "wEwEwE*E" * 4
    assert (big.ssm_layers, big.window_layers, big.cache_layers,
            big.routed_layers) == (0, 12, 4, 16)
    assert (big.cache_kv_heads, big.cache_k_dim, big.cache_v_dim) == (
        4, 128, 128)


def test_walk_scans_the_periods_with_the_window_pairs_inside():
    """One scan over the four periods, its body the period's own walk: a
    program holds (w, E), * and E once, not once a full layer (a chunk
    program compiles in half the time; 52 of them are the cell's boot)."""
    assert nemotron_h._rounds("wEwEwE*E" * 4) == ("wEwEwE*E", 4)
    assert nemotron_h._rounds(CFG.hybrid_pattern) == ("wEwEwE*E", 2)
    assert [(letters, n) for letters, n, _ in nemotron_h._plan(
        "wEwEwE*E")] == [("wE", 3), ("*", 1), ("E", 1)]
    # A period with a publisher in it, or a pattern that is no whole
    # number of periods, is walked as it was.
    for other in ("MEMEMEM*EMEMEMEM*EMEME", "MEMEM*EMEME",
                  get_config("tiny-phi4flash").hybrid_pattern,
                  "1-w-Y-*-g-x-" * 2, "MEMEMEME"):
        assert nemotron_h._rounds(other) == (other, 1)


# -- the two tables -----------------------------------------------------------

def test_yarn_table_at_the_published_numbers():
    """Frequencies 0-18 untouched, 35-63 divided by 16, the ramp linear
    between; the factor 0.1 ln 16 + 1 = 1.27726; the window layers' table
    plain. The program's table and the architecture file's agree."""
    cfg = published()
    big = serve_cell.model_config(cfg, ROOT)
    assert layers.yarn_ramp(big.rope_scaling, big.rope_theta, 128) == (18, 35)
    assert ARCH.yarn_ramp(cfg["rope_parameters"]["full_attention"],
                          128) == (18, 35)
    plain = np.asarray([500000.0 ** (-2.0 * i / 128) for i in range(64)])
    full, factor = layers.rope_table(big)
    window, one = layers.rope_table(big, window=True)
    assert one == 1.0
    # float32 arithmetic in the program: 1e-6 relative.
    np.testing.assert_allclose(np.asarray(window), plain, rtol=1e-6)
    ratio = np.asarray(full) / plain
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-6)
    want = 1 - (np.arange(19, 35) - 18) / 17 * (1 - 1 / 16)
    np.testing.assert_allclose(ratio[19:35], want, rtol=1e-5)
    assert factor == 1.2772588722239782
    assert abs(0.1 * math.log(16) + 1 - factor) < 1e-12
    ref_full, ref_factor = ARCH.rope_table(cfg, "full")
    np.testing.assert_allclose(np.asarray(full), ref_full, rtol=1e-6)
    assert ref_factor == factor
    assert ARCH.rope_table(cfg, "window") == (list(plain), 1.0)


def test_a_yarn_rule_has_no_single_table_and_llama3_is_what_it_was():
    with pytest.raises(ValueError, match="call rope_table"):
        layers.rope_frequencies(CFG)
    llama = get_config("llama3.1-8b")
    assert llama.rope_scaling.kind == "llama3"
    table, factor = layers.rope_table(llama)
    assert factor == 1.0
    np.testing.assert_array_equal(np.asarray(table),
                                  np.asarray(layers.rope_frequencies(llama)))
    # The factor multiplies cos and sin: a rotated vector is that much
    # longer, and q . k carries its square.
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 16))
    pos = jnp.arange(5)[None]
    inv_freq, f = layers.rope_table(CFG)
    np.testing.assert_allclose(
        np.asarray(layers.apply_rope(x, pos, inv_freq, f)),
        f * np.asarray(layers.apply_rope(x, pos, inv_freq)), rtol=1e-6)


# -- against the reference ----------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_program_equals_reference_through_ladder_rings_and_pages(plain,
                                                                  chunk):
    """Both samples of the check, with a chunk smaller than, equal to and
    larger than the window of 8: the harness's through one chunk of 32
    (four windows) and 8 decode steps, the long one (whole chunks and a
    padded last one, at least 3.32 windows, then 8 decode steps) through
    the chunk ladder, the install and decode; every compared position
    within 1e-4 (float32 on both sides: what is left is the order of the
    sums)."""
    sched, weights = plain
    sched = types.SimpleNamespace(**{**vars(sched), "prefill_chunk": chunk})
    file = tiny_file(chunk)
    system = ARCH.system_logits(sched, TOKENS, 32)
    ref, facts = ARCH.forward(file, TOKENS, weights)
    P, D = ARCH.long_shape(chunk, W)
    assert P >= 3.32 * W and P % chunk and D == 8
    assert system.long_logits.shape == facts["long_logits"].shape
    assert float(jnp.max(reference.position_errors(system.logits,
                                                   ref))) < 1e-4
    assert float(jnp.max(reference.position_errors(
        system.long_logits, facts["long_logits"]))) < 1e-4
    out = ARCH.compare(system, ref, {**facts, "n_prefill": 32}, file)
    assert out["ok"], out
    assert abs(out["window_edge"]) < 1e-3


def test_one_piece_prefill_equals_the_reference(plain):
    sched, weights = plain
    long = jnp.asarray(ARCH.long_tokens(TOKENS, 512, CHUNK, W))
    T = long.shape[1]
    cache = KVCache.create(CFG, 1, T, dtype=jnp.float32)
    logits, cache = prefill(sched._params, long, jnp.asarray([T]), cache)
    ref, _, _ = ARCH._stack(FILE, long, weights, W)
    assert float(jnp.max(reference.position_errors(logits, ref))) < 1e-4


def _decode_from(params, tokens, lens, steps, fused: bool):
    """Prefill ``tokens`` [B, S] (row b real to ``lens[b]``) in one batch,
    install rings and pages, then feed ``steps`` [B, n] one at a time or
    in one fused call whose sampler hands them back. Returns logits
    [B, n, V] of the decode steps (plain) or the pool's lengths (fused)
    and the pool."""
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

    # The sampler keeps each step's logits and hands back the next
    # scripted token (one more column, so that the last step has one).
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
def test_rows_on_both_sides_of_the_window_in_one_batch(plain, fused):
    """A row of 5 positions (inside the window of 8) beside one of 29
    (its rings wrapped three times) in one prefill batch, then 6 decode
    steps of both in one batch, plain and fused (the short row crosses
    the window's edge on its fourth step): each row's logits are the
    reference's on that row's own tokens."""
    sched, weights = plain
    rng = np.random.default_rng(7)
    lens = [5, 29]
    seqs = [rng.integers(0, 512, n + 6).astype(np.int32) for n in lens]
    S = 32
    tokens = np.zeros((2, S), np.int32)
    for b, (n, s) in enumerate(zip(lens, seqs)):
        tokens[b, :n] = s[:n]
    steps = jnp.asarray(np.stack([s[n: n + 6] for n, s in zip(lens, seqs)]))
    last, got, pool = _decode_from(sched._params, jnp.asarray(tokens), lens,
                                   steps, fused)
    assert list(np.asarray(pool.lengths)) == [11, 35]
    for b, (n, s) in enumerate(zip(lens, seqs)):
        ref, _, _ = ARCH._stack(FILE, jnp.asarray(s[None]), weights, W)
        want = ref[0, n - 1: n + 6]
        have = jnp.concatenate([last[b], got[b]], axis=0)
        assert float(jnp.max(reference.position_errors(have, want))) < 1e-4


def test_a_padded_row_leaves_its_ring_as_its_unpadded_run_would(plain):
    """Padding is never written: the ring a padded chunk leaves is the
    ring of the same positions prefilled without padding."""
    sched, _ = plain
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 512, (1, 21)),
                      jnp.int32)
    a = KVCache.create(CFG, 1, 21, dtype=jnp.float32)
    _, a = prefill(sched._params, ids, jnp.asarray([21]), a)
    b = KVCache.create(CFG, 1, 32, dtype=jnp.float32)
    _, b = prefill(sched._params, jnp.pad(ids, ((0, 0), (0, 11))),
                   jnp.asarray([21]), b)
    for x, y in ((a.state.win_k, b.state.win_k),
                 (a.state.win_v, b.state.win_v)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


def test_what_each_cache_holds():
    """A window layer's ring holds ``window`` positions a row at every
    length, its KV heads apart; the page pool is as deep as there are
    full layers; there is no recurrent state."""
    for slots, pages in ((3, 5), (7, 40)):
        pool = PagedKVCache.create(CFG, slots, pages, 16, quantized=True)
        assert pool.k.shape == (2, pages, 16, 2, 16)
        st = pool.state
        assert st.win_k.shape == st.win_v.shape == (6, slots + 1, 2, 8, 16)
        assert st.win_k.dtype == jnp.int8
        assert st.win_ks.shape == (6, slots + 1, 2, 8)
        assert st.ssm.size == st.conv.size == 0
        assert st.rows == slots + 1 and st.row_bytes == 0
        assert st.ring_position_bytes == 2 * 2 * (16 + 4)
    for width in (24, 200):
        small = KVCache.create(CFG, 2, width)
        assert small.k.shape == (2, 2, width, 2, 16)
        assert small.state.win_k.shape == (6, 2, 2, 8, 16)
        assert small.state.win_ks is None
    big = serve_cell.model_config(published(), ROOT)
    pool = jax.eval_shape(lambda: PagedKVCache.create(
        big, 32, 8193, 64, quantized=True))
    assert pool.k.shape == (4, 8193, 64, 4, 128)
    assert pool.state.win_k.shape == (12, 33, 4, 1024, 128)
    ring = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in pool.state[2:])
    assert ring == 12 * 33 * 1024 * ARCH.window_position_bytes(published())


@pytest.mark.parametrize("width", [16, 37, 5])
def test_an_int8_pool_of_four_heads_takes_its_tiles_a_token_at_a_time(width):
    """ops/paged_kv._tile_scatter writes an int8 pool of four KV heads x
    128 (the published model's) by (page, slot) a token, because the chip's
    compiler copies such a pool whole around a page-window scatter
    (PERF.md section 6, PR 40). It must hold what the window write holds:
    the same K and V through an eight-head pool, whose first four heads
    quantise alone (a scale a token a head), bit for bit; whole pages, a
    padded last tile and a span under a page."""
    import dataclasses
    four = dataclasses.replace(CFG, num_kv_heads=4, num_heads=4,
                               head_dim=128)
    eight = dataclasses.replace(CFG, num_kv_heads=8, num_heads=8,
                                head_dim=128)
    key = jax.random.PRNGKey(width)
    k = jax.random.normal(key, (2, 2, width, 8, 128), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape, jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    rows, lens = jnp.asarray([0, 1]), jnp.asarray([width, width - 3])
    pools = [write_prefill_batch(
        PagedKVCache.create(c, 2, 7, 16, max_pages_per_row=3,
                            dtype=jnp.float32, quantized=True),
        k[..., :g, :], v[..., :g, :], rows, lens, tables)
        for c, g in ((four, 4), (eight, 8))]
    assert pools[0].k.shape[3:] == (4, 128)
    for a, b in ((pools[0].k, pools[1].k[..., :4, :]),
                 (pools[0].v, pools[1].v[..., :4, :]),
                 (pools[0].k_scale, pools[1].k_scale[:, :, :4]),
                 (pools[0].v_scale, pools[1].v_scale[:, :, :4])):
        np.testing.assert_array_equal(np.asarray(a)[:, 1:],
                                      np.asarray(b)[:, 1:])


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
def test_rows_not_live_keep_their_rings_bit_for_bit(qparams, fused):
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
    for b, a in zip(before[2:], after.state[2:]):
        b, a = np.asarray(b), np.asarray(a)
        assert np.array_equal(b[:, 1], a[:, 1])      # the parked row
        assert not np.array_equal(b[:, 0], a[:, 0])
        assert not np.array_equal(b[:, 2], a[:, 2])
    # A live row's ring changed in the slots it wrote and nowhere else.
    wk0, wk1 = np.asarray(before.win_k), np.asarray(after.state.win_k)
    for row, length in ((0, 5), (2, 19)):
        wrote = {(length + j) % W for j in range(steps)}
        for slot in range(W):
            same = np.array_equal(wk0[:, row, :, slot],
                                  wk1[:, row, :, slot])
            assert same == (slot not in wrote)
    assert list(np.asarray(after.lengths)) == [5 + steps, 7, 19 + steps]


def test_routed_layer_is_dropless_and_counts_its_pairs(qparams):
    """Prefill counts: every real position's 2 pairs over 8 routed
    layers, none dropped; decode counts over the live rows."""
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 512, (2, 24)),
                      jnp.int32)
    lens = jnp.asarray([24, 9])
    valid = jnp.arange(24)[None, :] < lens[:, None]
    small = KVCache.create(CFG, 2, 24, dtype=jnp.float32)
    _, _, stats = jit_model(nemotron_h.prefill_counted, CFG)(
        qparams, ids, lens, small, valid)
    pairs = 33 * CFG.num_experts_per_tok * CFG.routed_layers
    assert [int(stats[0]), int(stats[1]), int(stats[2])] == [pairs, 0, pairs]
    pool = _filled_pool()
    _, _, st = jit_model(
        nemotron_h.decode_step_paged_touched, CFG,
        active=jnp.asarray([True, False, True]), pages=4)(
            qparams, jnp.asarray([[3], [4], [5]]), pool)
    assert int(st[1]) == CFG.num_experts * CFG.routed_layers
    assert int(st[2]) == int(st[3]) == 2 * 2 * CFG.routed_layers


# -- the routed layer's prefill: sorted tiles ----------------------------------

@pytest.mark.parametrize("shape,real", [((2, 24), (24, 9)), ((1, 16), (16,)),
                                        ((3, 4), (4, 0, 2))])
def test_tile_dispatch_equals_the_bucket_dispatch(plain, shape, real):
    """A prefill of this family (``live`` None: pairs sorted by expert,
    runs padded to tiles; since PR 43 the prefill half of
    pangu._routed_local itself) gives what the dropless buckets of its
    decode half give, padding sent nowhere, with the counts of the
    pairs: float32 on both sides, so what is left is the order of a
    token's k-term sum. Its last count is the rows of the filled
    tiles."""
    from p2p_llm_chat_tpu.models import pangu
    sched, _ = plain
    lp = nemotron_h._layer_view(sched._params["moe"], jnp.asarray(3))
    B, S = shape
    x = jax.random.normal(jax.random.PRNGKey(9), (B, S, CFG.hidden_size))
    counted = jnp.arange(S)[None, :] < jnp.asarray(real)[:, None]
    got, st = pangu._routed_local(x, lp, CFG, counted, None)
    want, _ = pangu._routed_local(x, lp, CFG, None, jnp.ones((B,), bool))
    want = jnp.where(counted[..., None], want, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    pairs = int(counted.sum()) * CFG.num_experts_per_tok
    tm = moe_tiles.tile_rows(B * S * CFG.num_experts_per_tok,
                             CFG.num_experts)
    assert list(np.asarray(st[:3])) == [pairs, 0, pairs]
    assert pairs <= int(st[3]) < pairs + CFG.num_experts * tm
    assert int(st[3]) % tm == 0
    assert not np.asarray(got)[~np.asarray(counted)].any()
    free, _ = pangu._routed_local(x, lp, CFG, None, None)
    want, _ = pangu._routed_local(x, lp, CFG, None, jnp.ones((B,), bool))
    np.testing.assert_allclose(np.asarray(free), np.asarray(want), atol=1e-5)


def _parent_tile_rows(pairs: int, experts: int) -> int:
    """nemotron_h._tile_rows as PR 40 left it (PR 42's parent)."""
    rows = 8
    while rows < 128 and rows * 2 * experts <= pairs:
        rows *= 2
    return rows


@pytest.mark.parametrize("tokens,parent,rows", [
    (16, 8, 16), (32, 8, 16), (64, 8, 16), (128, 16, 32), (256, 32, 64),
    (512, 64, 64), (1024, 128, 128), (2048, 128, 128), (16384, 128, 128)])
def test_tile_rows_over_mellums_real_buckets(tokens, parent, rows):
    """The rule at Mellum's real width (64 experts top-8) by the tokens
    of a dispatch: every bucket from the smallest to a two-row chunk and
    the longest ladder. PR 42 doubled the tile where the experts
    outnumber the mean run, so the programs UNDER 512 tokens a dispatch
    moved (tools/hash_programs.py cannot see it: tiny-mellum2 has 8
    experts); the 1,024-token chunks the cell's traffic drives, at one
    row and at two, and the 512 bucket did not."""
    pairs = tokens * 8
    assert _parent_tile_rows(pairs, 64) == parent
    assert moe_tiles.tile_rows(pairs, 64) == rows
    assert (rows == parent) == (tokens >= 512)


def test_tile_rows_at_the_test_sizes_are_the_parents():
    for pairs in (4, 32 * 2, 128 * 2, 256 * 2, 256 * 4, 4096):
        assert moe_tiles.tile_rows(pairs, 8) == _parent_tile_rows(pairs, 8)
    assert moe_tiles.tile_rows(32 * 2, 8) == 8
    assert moe_tiles.tile_rows(128 * 2, 8) == 32    # tiny-mellum2's chunk


def test_expert_kernel_walks_tiles_that_name_their_expert():
    """The expert-stripe kernel (interpret mode) over seven tiles of three
    experts' runs, the last tile empty: each filled tile is its rows times
    the expert ``source`` names; an empty tile comes back zeros."""
    from p2p_llm_chat_tpu.ops import quant_mm as qmm
    rng = np.random.default_rng(0)
    L, NE, H, F, tm = 2, 4, 256, 256, 8
    q = jnp.asarray(rng.integers(-127, 128, (L, NE, H, F), dtype=np.int8))
    s = jnp.asarray(rng.random((L, NE, 1, F), np.float32) * 0.02 + 0.005)
    source = jnp.asarray([0, 0, 2, 3, 3, 3, 3], jnp.int32)
    count = jnp.asarray([8, 3, 8, 8, 8, 1, 0], jnp.int32)
    x = jnp.asarray(rng.standard_normal((7, tm, H)).astype(np.float32))
    got = qmm.quant_matmul_experts_stacked(x, q, s, 1, count, source,
                                           interpret=True)
    ref = jnp.einsum("tch,thf->tcf", x,
                     q[1][source].astype(x.dtype)) * s[1][source]
    np.testing.assert_allclose(np.asarray(got[:6]), np.asarray(ref[:6]),
                               atol=1e-4, rtol=1e-4)
    assert not np.asarray(got[6]).any()


# -- the served precision, and the wrong models --------------------------------

def test_served_precision_passes(served):
    """Against the float32 reference on the same dequantised weights:
    prefill positions agree to float32's rounding (the carry is not
    quantised), decode positions read int8 rings and pages (7 bits a
    number and a scale a head: under 1% here)."""
    system, weights = served
    ref, facts = ARCH.forward(FILE, TOKENS, weights)
    sound = ARCH.compare(system, ref, {**facts, "n_prefill": 32}, FILE)
    assert sound["ok"], sound
    assert sound["median"] < 1e-4 and sound["long_median"] < 1e-4
    assert sound["p90"] < 0.02 and sound["long_max"] < 0.02
    assert abs(sound["window_edge"]) < 0.05


def test_bfloat16_activations_stay_inside_the_median_on_the_short_sample():
    """The cell's own precision at test size: the harness's sample (two
    sequences, so one flipped expert cannot take the median) reads under
    the limit; the one-sequence long sample is the chip's to judge."""
    system, weights = _served(jnp.bfloat16)
    ref, facts = ARCH.forward(FILE, TOKENS, weights)
    out = ARCH.compare(system, ref, {**facts, "n_prefill": 32}, FILE)
    assert out["median"] < ARCH.TOL_MEDIAN
    assert np.isfinite(out["long_max"]) and abs(out["window_edge"]) < 0.25


@pytest.mark.parametrize("name", ARCH.WRONG)
def test_wrong_model_reads_far_from_the_sound_one(served, name):
    """Every wrong model moves a reading by orders of magnitude against
    the sound program's (medians of 1e-6, an edge of 0.00): a window one
    key off by the edge, the others by a median of 4% or more. Where the
    limits lie between is set on the chip at the published widths."""
    system, weights = served
    cfg, w = ARCH.wrong_models(FILE, weights)[name]
    ref, facts = ARCH.forward(cfg, TOKENS, w)
    out = ARCH.compare(system, ref, {**facts, "n_prefill": 32}, FILE)
    if name in ("window_one_short", "window_one_long"):
        assert out["window_edge"] > 0.75 and not out["ok"]
    else:
        assert min(out["median"], out["long_median"]) > 0.04, out


def test_top_k_before_the_softmax_is_the_same_function():
    """ISSUE 40 lists it among the wrong models; with the kept weights
    divided by their sum it is the published function itself, so no limit
    may fail it: softmax over the 8 largest logits = the 8 largest of the
    softmax over all, renormalised."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (50, 64)) * 2
    probs = jax.nn.softmax(logits, -1)
    after, idx = jax.lax.top_k(probs, 8)
    after = after / jnp.sum(after, -1, keepdims=True)
    top, idx2 = jax.lax.top_k(logits, 8)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_allclose(np.asarray(jax.nn.softmax(top, -1)),
                               np.asarray(after), rtol=1e-5)


def test_boot_refuses_what_the_family_cannot_be():
    with pytest.raises(ValueError, match="LatentMoE.*or a plain one"):
        nemotron_h.init_params(CFG.with_(num_shared_experts=1),
                               jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="without rotary embedding"):
        nemotron_h.init_params(
            get_config("tiny-phi4flash").with_(attn_rope=True),
            jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="int8 or plain weights"):
        nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(0),
                                         quant="int4")
    assert isinstance(CFG, ModelConfig)
    assert CFG.rope_scaling == RopeScaling(
        kind="yarn", factor=4.0, original_max_position=16, beta_fast=2.0,
        beta_slow=0.02)

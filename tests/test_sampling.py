"""Sampling tests: JAX and numpy twins, boundary cases.

Boundary semantics under test (the ones that silently shape every served
reply): temperature<=0 greedy, top-k/top-p filtering including top_p<=0 and
top_p=1, large-vocab float tolerance (Generator.choice requires probability
sums exact to float64), and JAX/numpy agreement on the filtered support.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models.sampling import greedy, sample, sample_np


def logits_np(vocab=64, seed=0):
    return np.random.default_rng(seed).normal(size=(vocab,)).astype(np.float32)


def test_greedy_matches_argmax():
    lg = logits_np()
    assert sample_np(lg, np.random.default_rng(0)) == int(lg.argmax())
    out = sample(jnp.asarray(lg[None]), jax.random.PRNGKey(0))
    assert int(out[0]) == int(lg.argmax())
    assert int(greedy(jnp.asarray(lg[None]))[0]) == int(lg.argmax())


def test_large_vocab_temperature_does_not_crash():
    # float32 softmax sums fail Generator.choice's float64 tolerance at
    # ~128k vocab — regression for the float64 renormalisation.
    lg = logits_np(vocab=128256, seed=1)
    rng = np.random.default_rng(0)
    for _ in range(8):
        tok = sample_np(lg, rng, temperature=0.8)
        assert 0 <= tok < 128256


def test_top_k_restricts_support():
    lg = logits_np(vocab=32, seed=2)
    top5 = set(np.argsort(lg)[-5:].tolist())
    rng = np.random.default_rng(0)
    for _ in range(50):
        assert sample_np(lg, rng, temperature=1.0, top_k=5) in top5
    key = jax.random.PRNGKey(0)
    for i in range(20):
        key, sub = jax.random.split(key)
        assert int(sample(jnp.asarray(lg[None]), sub, temperature=1.0,
                          top_k=5)[0]) in top5


def test_top_k_one_is_greedy():
    lg = logits_np(seed=3)
    rng = np.random.default_rng(0)
    assert sample_np(lg, rng, temperature=1.0, top_k=1) == int(lg.argmax())


def test_top_p_zero_keeps_top_token():
    """top_p<=0 must degrade to top-1 (not crash, not uniform-random)."""
    lg = logits_np(seed=4)
    rng = np.random.default_rng(0)
    assert sample_np(lg, rng, temperature=1.0, top_p=0.0) == int(lg.argmax())
    out = sample(jnp.asarray(lg[None]), jax.random.PRNGKey(0),
                 temperature=1.0, top_p=0.0)
    assert int(out[0]) == int(lg.argmax())


def test_top_p_one_is_unfiltered():
    lg = np.array([0.0, 0.0, 10.0], np.float32)
    rng = np.random.default_rng(0)
    seen = {sample_np(lg, rng, temperature=5.0, top_p=1.0) for _ in range(200)}
    assert seen == {0, 1, 2}     # high temperature, no filtering


def test_top_p_small_keeps_only_peak():
    # One dominant token (p ~ 0.99): tiny top_p must exclude the tail.
    lg = np.array([10.0, 0.0, 0.0, 0.0], np.float32)
    rng = np.random.default_rng(0)
    for _ in range(50):
        assert sample_np(lg, rng, temperature=1.0, top_p=0.5) == 0
    key = jax.random.PRNGKey(1)
    for _ in range(20):
        key, sub = jax.random.split(key)
        assert int(sample(jnp.asarray(lg[None]), sub, temperature=1.0,
                          top_p=0.5)[0]) == 0


def test_top_p_keeps_prefix_reaching_mass():
    # Two tokens at ~0.45 each, rest tiny: top_p=0.6 needs both of the top
    # two (cum-probs < 0.6 admits the second at cum=0.45).
    lg = np.log(np.array([0.45, 0.45, 0.05, 0.05], np.float64)).astype(np.float32)
    rng = np.random.default_rng(0)
    seen = {sample_np(lg, rng, temperature=1.0, top_p=0.6) for _ in range(200)}
    assert seen == {0, 1}


def test_seeded_reproducibility():
    lg = logits_np(seed=5)
    r1, r2 = np.random.default_rng(7), np.random.default_rng(7)
    a = [sample_np(lg, r1, temperature=0.9, top_k=10) for _ in range(5)]
    b = [sample_np(lg, r2, temperature=0.9, top_k=10) for _ in range(5)]
    assert a == b
    assert len(set(a)) > 1     # the stream actually advances


# -- sample_batched: per-row device sampling (the fused-scheduler path) ------

def _keys(n, seed=0):
    return jax.vmap(jax.random.PRNGKey)(jnp.arange(seed, seed + n, dtype=jnp.uint32))


def test_batched_greedy_rows_match_argmax():
    from p2p_llm_chat_tpu.models.sampling import sample_batched
    lg = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)).astype(np.float32))
    toks, _ = sample_batched(lg, _keys(4), jnp.zeros(4), jnp.zeros(4, jnp.int32),
                             jnp.ones(4))
    assert np.array_equal(np.asarray(toks), np.asarray(lg).argmax(-1))


def test_batched_per_row_top_k_support():
    """Row 0 top_k=1 must always emit the argmax; row 1 top_k=3 stays
    within its top-3 set; row 2 unrestricted."""
    from p2p_llm_chat_tpu.models.sampling import sample_batched
    rng = np.random.default_rng(1)
    lg_np = rng.normal(size=(3, 32)).astype(np.float32)
    lg = jnp.asarray(lg_np)
    top3 = set(np.argsort(-lg_np[1])[:3].tolist())
    temps = jnp.asarray([1.0, 1.0, 1.0])
    tks = jnp.asarray([1, 3, 0], jnp.int32)
    tps = jnp.ones(3)
    seen1 = set()
    for i in range(50):
        toks, _ = sample_batched(lg, _keys(3, seed=i * 3), temps, tks, tps)
        t = np.asarray(toks)
        assert t[0] == lg_np[0].argmax()
        seen1.add(int(t[1]))
    assert seen1 <= top3 and len(seen1) > 1


def test_batched_top_p_excludes_tail():
    from p2p_llm_chat_tpu.models.sampling import sample_batched
    lg = jnp.asarray(np.array([[10.0, 0.0, 0.0, 0.0]], np.float32))
    for i in range(30):
        toks, _ = sample_batched(lg, _keys(1, seed=i), jnp.ones(1),
                                 jnp.zeros(1, jnp.int32), jnp.asarray([0.5]))
        assert int(toks[0]) == 0


def test_batched_top_p_zero_degrades_to_top1():
    from p2p_llm_chat_tpu.models.sampling import sample_batched
    lg = jnp.asarray(np.random.default_rng(3).normal(size=(2, 16)).astype(np.float32))
    toks, _ = sample_batched(lg, _keys(2), jnp.ones(2), jnp.zeros(2, jnp.int32),
                             jnp.zeros(2))
    assert np.array_equal(np.asarray(toks), np.asarray(lg).argmax(-1))


def test_batched_keys_advance_and_reproduce():
    from p2p_llm_chat_tpu.models.sampling import sample_batched
    lg = jnp.asarray(np.random.default_rng(4).normal(size=(2, 256)).astype(np.float32))
    args = (jnp.ones(2), jnp.zeros(2, jnp.int32), jnp.ones(2))
    k0 = _keys(2, seed=9)
    t1, k1 = sample_batched(lg, k0, *args)
    t1b, k1b = sample_batched(lg, k0, *args)
    assert np.array_equal(np.asarray(t1), np.asarray(t1b))      # same key, same draw
    assert np.array_equal(np.asarray(k1), np.asarray(k1b))
    t2, _ = sample_batched(lg, k1, *args)
    seq = [int(x) for x in np.asarray(jnp.concatenate([t1, t2]))]
    assert len(set(seq)) > 1     # stream advances across key updates


def test_apply_repeat_penalty_matches_numpy_twin():
    from p2p_llm_chat_tpu.models.sampling import apply_repeat_penalty

    rng = np.random.default_rng(0)
    B, V, R = 3, 32, 8
    logits = rng.normal(size=(B, V)).astype(np.float32)
    ring = np.full((B, R), V, np.int32)          # sentinel = empty
    ring[0, :3] = [1, 5, 1]                      # dup entry: penalise once
    ring[1, :2] = [0, 31]
    rp = np.asarray([1.3, 2.0, 1.0], np.float32) # row 2: identity
    got = np.asarray(apply_repeat_penalty(
        jnp.asarray(logits), jnp.asarray(ring), jnp.asarray(rp)))
    for b in range(B):
        want = logits[b].astype(np.float64).copy()
        for t in set(int(x) for x in ring[b] if x < V):
            want[t] = want[t] / rp[b] if want[t] > 0 else want[t] * rp[b]
        np.testing.assert_allclose(got[b], want.astype(np.float32),
                                   rtol=1e-6, atol=1e-6)


# -- sample_batched: the candidate sort runs only where a live row samples --

def _sample_batched_before_the_cond(logits, keys, temperature, top_k, top_p,
                                    top_c=64, ring=None, rp=None):
    """``sample_batched`` as it stood before the sort went under a
    ``cond`` (PR 52), kept here as the oracle: every row sorted, warped
    and drawn from, and the choice made per element after the work."""
    from p2p_llm_chat_tpu.models.layers import NEG_INF
    from p2p_llm_chat_tpu.models.sampling import _warp, apply_repeat_penalty
    B, V = logits.shape
    if ring is not None:
        logits = apply_repeat_penalty(logits, ring, rp)
    C = min(top_c, V)
    sorted_logits, order = jax.lax.top_k(logits, C)
    wprobs = _warp(sorted_logits, temperature, top_k, top_p)
    split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    new_keys, subs = split[:, 0], split[:, 1]
    choice = jax.vmap(jax.random.categorical)(
        subs, jnp.where(wprobs > 0, jnp.log(wprobs), NEG_INF))
    sampled = jnp.take_along_axis(order, choice[:, None], axis=-1)[:, 0]
    tok = jnp.where(temperature <= 0.0,
                    jnp.argmax(logits, axis=-1), sampled).astype(jnp.int32)
    return tok, new_keys


_TEMPS = {"none-samples": [0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
          "some-sample": [0.0, 0.8, 0.0, 1.3, 0.0, 0.0],
          "all-sample": [0.8, 0.8, 1.0, 1.3, 0.7, 0.5]}


@pytest.mark.parametrize("dead_row", [False, True],
                         ids=["all-live", "dead-row-at-0.8"])
@pytest.mark.parametrize("penalty", [False, True],
                         ids=["no-penalty", "penalty"])
@pytest.mark.parametrize("case", sorted(_TEMPS))
def test_batched_tokens_and_keys_are_the_unconditional_sampler_s(
        case, penalty, dead_row):
    """Tokens of the live rows and every row's advanced key, bit for
    bit, over three steps of carried keys, whether the step's ``cond``
    took the sort or the argmax alone. The dead row (a released slot
    whose last request sampled at 0.8) is masked out by ``live``: it must
    not force the sort, its key still splits, and nobody reads its
    token."""
    from p2p_llm_chat_tpu.models.sampling import sample_batched
    B, V, R = 6, 300, 8
    rng = np.random.default_rng(52)
    temps = np.asarray(_TEMPS[case], np.float32)
    live = np.ones(B, bool)
    if dead_row:
        temps[5], live[5] = 0.8, False
    top_k = jnp.asarray([0, 40, 0, 5, 0, 40], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9, 1.0, 0.5, 1.0, 0.9], jnp.float32)
    kw = {}
    if penalty:
        ring = np.full((B, R), V, np.int32)
        ring[:, :4] = rng.integers(0, V, size=(B, 4))
        kw = dict(ring=jnp.asarray(ring),
                  rp=jnp.asarray([1.3, 1.1, 1.0, 2.0, 1.5, 1.2], jnp.float32))
    new = jax.jit(lambda lg, k: sample_batched(
        lg, k, jnp.asarray(temps), top_k, top_p,
        live=jnp.asarray(live) if dead_row else None, **kw))
    old = jax.jit(lambda lg, k: _sample_batched_before_the_cond(
        lg, k, jnp.asarray(temps), top_k, top_p, **kw))
    keys_new = keys_old = _keys(B, seed=11)
    for _ in range(3):
        lg = jnp.asarray(rng.normal(size=(B, V)).astype(np.float32) * 3.0)
        toks_new, keys_new = new(lg, keys_new)
        toks_old, keys_old = old(lg, keys_old)
        assert toks_new.dtype == toks_old.dtype == jnp.int32
        assert np.array_equal(np.asarray(toks_new)[live],
                              np.asarray(toks_old)[live])
        assert np.array_equal(np.asarray(keys_new), np.asarray(keys_old))


def _sorts_by_case_branch(module) -> dict:
    """Where a lowered module sorts: {None or (branch index of the
    enclosing ``stablehlo.case``): count} over every ``chlo.top_k`` and
    ``stablehlo.sort`` of every function (a ``cond`` lowers to a
    ``case`` whose regions hold the branches inline)."""
    found: dict = {}

    def walk(op, branch):
        name = op.operation.name
        if name in ("chlo.top_k", "stablehlo.sort"):
            found[branch] = found.get(branch, 0) + 1
        for i, region in enumerate(op.operation.regions):
            inner = i if name == "stablehlo.case" else branch
            for block in region.blocks:
                for child in block.operations:
                    walk(child, inner)
    walk(module, None)
    return found


def test_the_candidate_sort_sits_in_a_conditional_s_branch():
    """In the program ``sample_batched`` lowers to, the ``top_k`` lies
    in the taken branch of the one ``case`` and nowhere else: not in the
    main computation, which keeps the penalty, the argmax and the key
    split, and not in the branch a step of greedy rows runs. The same
    walk finds the sort outside any ``case`` in the sampler as it stood,
    so it can tell."""
    from p2p_llm_chat_tpu.models.sampling import sample_batched
    B, V, R = 4, 300, 8
    args = (jnp.zeros((B, V), jnp.float32), _keys(B), jnp.zeros(B),
            jnp.zeros(B, jnp.int32), jnp.ones(B))
    kw = dict(ring=jnp.full((B, R), V, jnp.int32), rp=jnp.ones(B))
    new = jax.jit(lambda live, *a: sample_batched(*a, live=live, **kw))
    for live in (None, jnp.ones(B, bool)):
        lowered = new.lower(live, *args)
        assert lowered.as_text().count("stablehlo.case") == 1
        assert _sorts_by_case_branch(
            lowered.compiler_ir(dialect="stablehlo")) == {1: 1}
    before = jax.jit(lambda *a: _sample_batched_before_the_cond(
        *a, **kw)).lower(*args)
    assert _sorts_by_case_branch(
        before.compiler_ir(dialect="stablehlo")) == {None: 1}

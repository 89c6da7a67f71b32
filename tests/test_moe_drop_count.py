"""What the capacity buckets drop is counted, and only for real prompt
positions (``moe_mlp``'s ``valid``; ``serve_moe_assignments_total`` and
``serve_moe_dropped_total`` on /metrics).

The scenario is an admission at width 8: a short request in the first
entry, a long one in the second, six dummy entries behind them. The
short entry's padding positions come before the long entry's tokens in
the (token, slot) order the buckets fill in, so they take slots: the
count must leave them out, and must report every real pair they
displaced. The count is checked against a recount in numpy of the same
routing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import mixtral
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache

R, S, H, NE, K = 8, 16, 32, 8, 4
LENS = np.array([3, 16, 1, 1, 1, 1, 1, 1])      # short, long, six dummies
REAL_ROWS = np.array([True, True] + [False] * 6)


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((R, S, H)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((H, NE)), jnp.float32)
    w_gate = jnp.asarray(rng.standard_normal((NE, H, 16)) * 0.1, jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((NE, H, 16)) * 0.1, jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((NE, 16, H)) * 0.1,
                         jnp.float32)
    valid = (np.arange(S)[None, :] < LENS[:, None]) & REAL_ROWS[:, None]
    return x, router, (w_gate, w_up, w_down), valid


def _recount(x, router, valid, capacity):
    """Fill the buckets in (token, slot) order by hand. Returns (real
    pairs, real pairs dropped, real pairs dropped that would have fitted
    had no padding position taken a slot)."""
    probs = jax.nn.softmax(np.asarray(x).reshape(R * S, H) @ np.asarray(
        router), axis=-1)
    top = np.asarray(jax.lax.top_k(probs, K)[1])
    real = valid.reshape(R * S)
    load, load_real = np.zeros(NE, int), np.zeros(NE, int)
    dropped = displaced = 0
    for t in range(R * S):
        for e in top[t]:
            fits = load[e] < capacity
            if real[t]:
                dropped += not fits
                displaced += (not fits) and load_real[e] < capacity
                load_real[e] += 1
            load[e] += 1
    return int(real.sum()) * K, dropped, displaced


@pytest.mark.parametrize("capacity", [None, 12, 5, 1])
def test_count_is_of_real_positions_and_reports_what_padding_displaced(
        capacity):
    x, router, (w_gate, w_up, w_down), valid = _inputs()
    out, stats = mixtral.moe_mlp_counted(x, router, w_gate, w_up, w_down, K,
                                         jnp.asarray(valid),
                                         capacity=capacity)
    plain = mixtral.moe_mlp(x, router, w_gate, w_up, w_down, K,
                            capacity=capacity)
    pairs, dropped, displaced = _recount(x, router, valid,
                                         R * S if capacity is None
                                         else capacity)
    assert pairs == (3 + 16) * K        # neither padding nor dummy rows
    assert list(np.asarray(stats[:2])) == [pairs, dropped]
    if capacity is None:
        # Dropless, the counted form leaves the buckets for sorted tiles
        # (mixtral._moe_tiles) and the maskless one, ``plain``, keeps
        # them: the real positions' output is the buckets' to rounding,
        # padding is sent nowhere and gets 0, and the count's third
        # entry is the tile rows multiplied.
        assert dropped == 0
        np.testing.assert_allclose(np.asarray(out)[valid],
                                   np.asarray(plain)[valid], atol=1e-5)
        assert not np.asarray(out)[~valid].any()
        assert stats.shape == (3,) and pairs <= int(stats[2])
        return
    else:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
        assert stats.shape == (2,)
        # The scenario does what it is for: padding ahead of the long
        # row's tokens cost real pairs their slots, and they are in the
        # count.
        assert dropped > 0 and 0 < displaced <= dropped
    # Silent loss is what the count exists to rule out: a real position
    # whose output differs from the dropless one has a dropped pair.
    exact = mixtral.moe_mlp(x, router, w_gate, w_up, w_down, K)
    changed = np.any(np.asarray(out) != np.asarray(exact), axis=-1) & valid
    assert (changed.sum() > 0) == (int(stats[1]) > 0)
    assert changed.sum() <= int(stats[1])


def test_prefill_and_chunks_sum_the_count_over_layers():
    """Through the model's own prefill programs (whole prompt, and the
    same prompt as two continuation chunks) the count is per layer and
    adds up: pairs = real positions x top-k x layers."""
    cfg = get_config("tiny-olmoe").with_(moe_capacity_factor=0.1)   # C = 6
    params = mixtral.init_params(cfg, jax.random.PRNGKey(2),
                                 dtype=jnp.float32)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(R, S)),
                         jnp.int32)
    lens = jnp.asarray(LENS, jnp.int32)
    valid = jnp.asarray((np.arange(S)[None, :] < LENS[:, None])
                        & REAL_ROWS[:, None])
    per_token = cfg.num_experts_per_tok * cfg.num_layers
    counted, _, whole = mixtral.prefill_counted(
        params, cfg, tokens, lens, KVCache.create(cfg, R, S, jnp.float32),
        valid)
    assert int(whole[0]) == 19 * per_token and int(whole[1]) > 0
    # Counting changes nothing the uncounted program computes.
    plain, _ = mixtral.prefill(params, cfg, tokens, lens,
                               KVCache.create(cfg, R, S, jnp.float32))
    np.testing.assert_array_equal(np.asarray(counted), np.asarray(plain))
    cache = KVCache.create(cfg, R, S, jnp.float32)
    total = 0
    for off in (0, S // 2):
        _, cache, part = mixtral.prefill_chunk_counted(
            params, cfg, tokens[:, off:off + S // 2], cache, off,
            valid[:, off:off + S // 2])
        total += int(part[0])
    assert total == 19 * per_token

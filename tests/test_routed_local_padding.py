"""A prefill's padding is sent nowhere in the held-range dispatch
(models/pangu._routed_local, which models/pangu.py and
models/nemotron_h.py share; since PR 43 its prefill half is the
sorted-tile dispatch of models/moe_tiles.py): a position outside
``counted`` takes no tile row, so only real pairs are multiplied, and
the prefill's last count says how many rows that took (filled tiles x
rows a tile). Until PR 43 that count said whether a layer ran the all-T
buckets; the cases are the ones that fenced that branch, held to what
the tiles do in its place.

Two levels, both families' tiny configurations. One layer's dispatch on
plain float32 weights with a router made for the case: every padding
position carries one vector ``pad``, the real ones are orthogonal to
it, and held expert 0's router column gains a multiple of ``pad``, so
all the padding chooses expert 0 and no real position's choice moves.
Then the whole model as the scheduler calls it (``prefill_counted``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import moe_tiles, nemotron_h, pangu
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache, _layer_view

from solo import jit_model

FAMILIES = {"tiny-pangu": (pangu, "layers"),
            "tiny-nemotron-h": (nemotron_h, "moe")}
T, REAL = 128, 64
SMALL = 32      # a quarter of T: what tripped the all-T branch (PR 33)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def layer(request):
    """(config, one routed layer's weights with the made router,
    x [1,T,H]: REAL real positions then the padding's one vector)."""
    cfg = get_config(request.param)
    model, tree = FAMILIES[request.param]
    params = model.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    lp = dict(_layer_view(params[tree], jnp.asarray(0, jnp.int32)))
    H = cfg.hidden_size
    kx, kp = jax.random.split(jax.random.PRNGKey(5))
    pad = jax.random.normal(kp, (H,), jnp.float32)
    unit = pad / jnp.linalg.norm(pad)
    real = jax.random.normal(kx, (REAL, H), jnp.float32)
    real = real - (real @ unit)[:, None] * unit
    lp["router"] = lp["router"].at[:, 0].add(30.0 * unit
                                             / jnp.linalg.norm(pad))
    x = jnp.concatenate([real, jnp.broadcast_to(pad, (T - REAL, H))])[None]
    return cfg, lp, x


def dispatch(cfg, lp, x, counted):
    latent = None
    if cfg.moe_latent_size:
        latent = x @ lp["w_fc1"]
    out, stats = jax.jit(
        lambda x, latent, counted: pangu._routed_local(
            x, lp, cfg, counted, None, latent))(x, latent, counted)
    return np.asarray(out, np.float32), [int(n) for n in stats]


def dense(cfg, lp, x):
    """The held experts' part of the routed sum with no buckets: every
    position through every held expert, weighed by the router's choice."""
    xt = x.reshape(-1, x.shape[-1])
    top_w, top_i = pangu.route(xt, lp["router"], cfg, lp.get("router_bias"))
    inp = xt @ lp["w_fc1"] if cfg.moe_latent_size else xt
    out = 0.0
    for e in range(cfg.num_experts):
        if cfg.mlp_activation == "relu2":
            act = jnp.square(jax.nn.relu(inp @ lp["w_up_e"][e]))
        else:
            gu = inp @ lp["wgu_e"][e]
            F = gu.shape[-1] // 2
            act = jax.nn.silu(gu[:, :F]) * gu[:, F:]
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=1)
        out = out + w[:, None] * (act @ lp["w_down"][e])
    return np.asarray(out, np.float32), np.asarray(top_i)


def mask(n_real=REAL):
    return (jnp.arange(T) < n_real)[None]


def tile_rows_of(cfg, top_i, positions: int = T):
    """Filled tiles x rows a tile for the pairs ``top_i`` [n,k] of a
    dispatch of ``positions``, recounted from the routing."""
    NE = cfg.num_experts
    sent = np.bincount(top_i[top_i < NE], minlength=NE)
    tm = moe_tiles.tile_rows(positions * cfg.num_experts_per_tok, NE,
                             cfg.router_width)
    return int(np.sum(-(-sent // tm)) * tm)


def test_the_made_router_sends_the_padding_to_one_held_expert(layer):
    """What the other cases stand on: all the padding chooses held
    expert 0, and the real positions alone overload nothing."""
    cfg, lp, x = layer
    _, top_i = dense(cfg, lp, x)
    assert (top_i[REAL:] == 0).any(axis=1).all()
    held = top_i[:REAL][top_i[:REAL] < cfg.num_experts]
    assert np.bincount(held, minlength=cfg.num_experts).max() <= SMALL


def test_padding_takes_no_row_and_the_real_pairs_alone_are_multiplied(layer):
    """Half the dispatch is padding that agrees on a held expert: with
    ``counted`` the tiles hold the real positions' pairs alone (the
    last count is their filled tiles), the real positions read what the
    bucketless sum gives and the padding reads 0; without it the
    padding's pairs fill tiles of expert 0 too, and are served."""
    cfg, lp, x = layer
    want, top_i = dense(cfg, lp, x)
    out, stats = dispatch(cfg, lp, x, mask())
    assert stats[3] == tile_rows_of(cfg, top_i[:REAL])
    np.testing.assert_allclose(out[0, :REAL], want[:REAL], rtol=2e-4,
                               atol=2e-5)
    assert not out[0, REAL:].any()
    unmasked, stats_all = dispatch(cfg, lp, x, None)
    assert stats_all[3] == tile_rows_of(cfg, top_i) >= stats[3] + T - REAL
    np.testing.assert_allclose(unmasked[0], want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(out[0, :REAL], unmasked[0, :REAL], rtol=2e-5,
                               atol=2e-6)


def test_a_dummy_entry_sends_nothing(layer):
    """A row with no counted position (an admission's dummy entry)
    behind a real one, and a dispatch of nothing else (warm-up): no
    pair, no output, no count, no tile."""
    cfg, lp, x = layer
    two = x.reshape(2, REAL, -1)
    counted = jnp.asarray([True, False])[:, None] & jnp.ones((2, REAL), bool)
    out, stats = dispatch(cfg, lp, two, counted)
    want, top_i = dense(cfg, lp, x)
    np.testing.assert_allclose(out[0], want[:REAL], rtol=2e-4, atol=2e-5)
    assert not out[1].any()
    k = cfg.num_experts_per_tok
    assert stats == [int((top_i[:REAL] < cfg.num_experts).sum()), 0,
                     REAL * k, tile_rows_of(cfg, top_i[:REAL])]
    out, stats = dispatch(cfg, lp, x, jnp.zeros((1, T), bool))
    assert not out.any() and stats == [0, 0, 0, 0]


@pytest.mark.parametrize("n_real", [REAL, 40, T])
def test_the_pair_counts_are_what_they_were(layer, n_real):
    """Counts 0 and 2 run over the counted positions as before: the
    pairs they routed to held experts, and k a position; the last is
    those pairs' tiles, never fewer rows than pairs."""
    cfg, lp, x = layer
    _, top_i = dense(cfg, lp, x)
    _, stats = dispatch(cfg, lp, x, mask(n_real))
    assert stats[0] == int((top_i[:n_real] < cfg.num_experts).sum())
    assert stats[1] == 0
    assert stats[2] == n_real * cfg.num_experts_per_tok
    assert stats[3] == tile_rows_of(cfg, top_i[:n_real]) >= stats[0]


def test_a_real_overload_fills_more_tiles_and_drops_nothing(layer):
    """The same positions all counted: expert 0 is really sent more
    than a quarter of the dispatch (the case the all-T branch existed
    for), its run takes the tiles it needs and every pair is served."""
    cfg, lp, x = layer
    want, top_i = dense(cfg, lp, x)
    assert (top_i == 0).sum() > SMALL
    out, stats = dispatch(cfg, lp, x, mask(T))
    assert stats[3] == tile_rows_of(cfg, top_i)
    assert stats[3] < cfg.num_experts * T          # the all-T buckets' rows
    np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-5)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request):
    cfg = get_config(request.param)
    model, _ = FAMILIES[request.param]
    params = model.init_params_quantized(cfg, jax.random.PRNGKey(7),
                                         dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg.vocab_size, (1, REAL)), jnp.int32)
    return cfg, model, params, tokens


def test_a_padded_prefill_is_the_unpadded_run_at_its_real_positions(served):
    """The whole model as the scheduler calls it, one row of T positions
    of which half are token 0 behind the prompt, and a dummy entry: the
    real positions' logits and the pair counts are the unpadded run's,
    and the rows multiplied are the real pairs' tiles, which the
    padding and the dummy entry add nothing to."""
    cfg, model, params, tokens = served
    solo = KVCache.create(cfg, 1, REAL, dtype=jnp.float32)
    prefill_counted = jit_model(model.prefill_counted, cfg)
    want, _, stats_solo = prefill_counted(
        params, tokens, jnp.asarray([REAL]), solo, jnp.ones((1, REAL), bool))
    padded = jnp.zeros((2, T), jnp.int32).at[0, :REAL].set(tokens[0])
    counted = mask() & jnp.asarray([True, False])[:, None]
    cache = KVCache.create(cfg, 2, T, dtype=jnp.float32)
    got, _, stats = prefill_counted(
        params, padded, jnp.asarray([REAL, 1]), cache, counted)
    np.testing.assert_allclose(np.asarray(got[0, :REAL]),
                               np.asarray(want[0]), rtol=2e-3, atol=2e-3)
    stats, stats_solo = np.asarray(stats), np.asarray(stats_solo)
    assert (stats[0], stats[2]) == (stats_solo[0], stats_solo[2])
    assert stats[2] == REAL * cfg.num_experts_per_tok * cfg.routed_layers
    k, NE = cfg.num_experts_per_tok, cfg.num_experts
    tm = moe_tiles.tile_rows(2 * T * k, NE, cfg.router_width)
    assert stats[0] <= stats[3] < stats[0] + cfg.routed_layers * NE * tm
    assert stats[3] % tm == 0


def test_routed_layers_are_the_layers_that_route():
    assert get_config("tiny-pangu").routed_layers == 2
    assert get_config("tiny-nemotron-h").routed_layers == 5
    assert get_config("nemotron-3-super-120b-a12b-l22e128"
                      ).routed_layers == 10
    assert get_config("openpangu-ultra-moe-718b-l9e16").routed_layers == 8
    assert get_config("tiny").routed_layers == 0
    assert get_config("tiny-moe").routed_layers == get_config(
        "tiny-moe").num_layers

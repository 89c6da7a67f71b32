"""The held-range families' prefill on the sorted-tile dispatch
(models/pangu._routed_local with ``live`` None, since PR 43 a call of
models/moe_tiles.routed_tiles): what the tiles newly carry for
openPangu and for Nemotron's LatentMoE, one routed layer on plain
float32 weights at the tiny sizes, held to a float32 ``jax.numpy`` sum
over the held experts with a router written out here, and to the
parent's result: buckets that hold every row, which the decode half of
the same function still is (``live`` all true).

tests/test_routed_local_padding.py has the padding's cases,
tests/test_mellum_parity.py the third family's, tests/test_moe_tiles.py
the dispatch under the Mixtral family's router."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import moe_tiles, nemotron_h, pangu
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import _layer_view

FAMILIES = {"tiny-pangu": (pangu, "layers"),
            "tiny-nemotron-h": (nemotron_h, "moe")}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def layer(request):
    """(config, one routed layer's plain float32 weights)."""
    cfg = get_config(request.param)
    model, tree = FAMILIES[request.param]
    params = model.init_params(cfg, jax.random.PRNGKey(11),
                               dtype=jnp.float32)
    return cfg, dict(_layer_view(params[tree], jnp.asarray(1, jnp.int32)))


def _x(cfg, B, S, seed=2):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (B, S, cfg.hidden_size), jnp.float32)


def _inputs(cfg, lp, x):
    """What the experts read: the latent's rows where they live in one."""
    return x @ lp["w_fc1"] if cfg.moe_latent_size else None


def tiles(cfg, lp, x, counted=None):
    out, stats = jax.jit(lambda x, latent, counted: pangu._routed_local(
        x, lp, cfg, counted, None, latent))(x, _inputs(cfg, lp, x), counted)
    return np.asarray(out, np.float32), [int(n) for n in stats]


def buckets(cfg, lp, x):
    """The parent's dropless result: every expert's bucket holds every
    row (``run(T)`` of the function PR 43 split; its decode half)."""
    out, _ = jax.jit(lambda x, latent: pangu._routed_local(
        x, lp, cfg, None, jnp.ones((x.shape[0],), bool), latent))(
        x, _inputs(cfg, lp, x))
    return np.asarray(out, np.float32)


def routing(cfg, lp, x):
    """The router by hand, numpy float64: sigmoid scores over all
    ``router_width`` experts, the k largest of score (+ bias) chosen,
    weighed by the unbiased scores over their sum, times the scaling
    factor. Returns (top_w [T,k], top_i [T,k])."""
    xt = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    scores = 1.0 / (1.0 + np.exp(-(xt @ np.asarray(lp["router"],
                                                   np.float64))))
    pick = scores + (np.asarray(lp["router_bias"], np.float64)
                     if "router_bias" in lp else 0.0)
    top_i = np.argsort(-pick, axis=-1, kind="stable")[
        :, :cfg.num_experts_per_tok]
    top_w = np.take_along_axis(scores, top_i, axis=-1)
    top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    return top_w * cfg.routed_scaling_factor, top_i


def reference(cfg, lp, x):
    """Every position through every held expert, weighed by the hand
    router's choice; selections past ``num_experts`` add nothing."""
    top_w, top_i = routing(cfg, lp, x)
    inp = _inputs(cfg, lp, x)
    inp = (x if inp is None else inp).reshape(len(top_i), -1)
    out = 0.0
    for e in range(cfg.num_experts):
        if cfg.mlp_activation == "relu2":
            act = jnp.square(jax.nn.relu(inp @ lp["w_up_e"][e]))
        else:
            gu = inp @ lp["wgu_e"][e]
            F = gu.shape[-1] // 2
            act = jax.nn.silu(gu[:, :F]) * gu[:, F:]
        w = jnp.asarray(np.where(top_i == e, top_w, 0.0).sum(1), jnp.float32)
        out = out + w[:, None] * (act @ lp["w_down"][e])
    return np.asarray(out, np.float32), top_i


def _rows(cfg, top_i, positions):
    NE = cfg.num_experts
    sent = np.bincount(top_i[top_i < NE], minlength=NE)
    tm = moe_tiles.tile_rows(positions * cfg.num_experts_per_tok, NE,
                             cfg.router_width)
    return int(np.sum(-(-sent // tm)) * tm)


@pytest.mark.parametrize("B,S", [(2, 24), (1, 64), (4, 32)])
def test_tiles_give_the_reference_and_the_buckets(layer, B, S):
    """A held range behind a wider router (the selection bias, the
    latent and the ungated experts where the family has them): the
    tiles' sum is the float32 reference's and the all-row buckets', and
    the counts are the pairs held, none dropped, the pairs routed, and
    the filled tiles' rows."""
    cfg, lp = layer
    x = _x(cfg, B, S)
    want, top_i = reference(cfg, lp, x)
    got, stats = tiles(cfg, lp, x)
    width = cfg.moe_latent_size or cfg.hidden_size
    assert got.shape == (B, S, width)
    np.testing.assert_allclose(got.reshape(B * S, width), want, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(got, buckets(cfg, lp, x), rtol=2e-5,
                               atol=2e-6)
    held = int((top_i < cfg.num_experts).sum())
    assert 0 < held < top_i.size
    assert stats == [held, 0, top_i.size, _rows(cfg, top_i, B * S)]


def test_a_position_routed_wholly_elsewhere_gets_nothing(layer):
    """Selections past ``num_experts`` take no row: positions whose k
    choices all lie with other chips read exactly 0, and count as
    routed but not as held."""
    cfg, lp = layer
    x = _x(cfg, 1, 48, seed=4)
    NE = cfg.num_experts
    # Push every held expert's score under every other's for half the
    # positions: a large negative logit along a direction they share.
    away = jax.random.normal(jax.random.PRNGKey(6), (cfg.hidden_size,))
    away = away / jnp.linalg.norm(away)
    x = x.at[0, :24].add(40.0 * away)
    lp = {**lp, "router": lp["router"].at[:, :NE].add(-away[:, None])}
    want, top_i = reference(cfg, lp, x)
    assert (top_i[:24] >= NE).all() and (top_i[24:] < NE).any()
    got, stats = tiles(cfg, lp, x)
    assert not got[0, :24].any()
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    assert stats[0] == int((top_i < NE).sum()) and stats[2] == top_i.size


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    """``router_bias`` decides which experts a position takes and stays
    out of their weights: the tiles follow the hand router with the
    bias, and a layer without it gives another sum."""
    cfg = get_config("tiny-nemotron-h")
    assert cfg.moe_selection_bias
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(11),
                                    dtype=jnp.float32)
    lp = dict(_layer_view(params["moe"], jnp.asarray(1, jnp.int32)))
    lp["router_bias"] = jnp.where(jnp.arange(cfg.router_width) % 2 == 0,
                                  0.5, -0.5).astype(jnp.float32)
    x = _x(cfg, 1, 40, seed=8)
    want, top_i = reference(cfg, lp, x)
    assert (top_i % 2 == 0).mean() > 0.9          # the bias chose
    got, _ = tiles(cfg, lp, x)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    plain = {k: v for k, v in lp.items() if k != "router_bias"}
    other, top_plain = reference(cfg, plain, x)
    assert (top_plain != top_i).any()
    assert np.abs(other - want).max() > 1e-3


@pytest.mark.parametrize("B,S", [(1, 5), (2, 3), (1, 1)])
def test_a_wake_of_a_few_positions(layer, B, S):
    """A session wake's suffix (``counted`` None, ``live`` None, under
    8 positions in all): fewer pairs than a tile has rows."""
    cfg, lp = layer
    x = _x(cfg, B, S, seed=5)
    want, top_i = reference(cfg, lp, x)
    got, stats = tiles(cfg, lp, x)
    np.testing.assert_allclose(got.reshape(B * S, -1), want, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(got, buckets(cfg, lp, x), rtol=2e-5,
                               atol=2e-6)
    assert stats[3] == _rows(cfg, top_i, B * S)


def test_one_hot_expert_takes_as_many_tiles_as_it_needs(layer):
    """A skewed router: every position chooses held expert 1, far more
    than the quarter of the dispatch that used to send the whole layer
    to buckets of every position. Its run spans several tiles, the
    other experts' runs are untouched, nothing is dropped."""
    cfg, lp = layer
    B, S = 4, 64
    x = _x(cfg, B, S, seed=7)
    hot = jax.random.normal(jax.random.PRNGKey(8), (cfg.hidden_size,))
    hot = hot / jnp.linalg.norm(hot)
    x = x + 40.0 * hot
    lp = {**lp, "router": lp["router"].at[:, 1].set(hot)}
    want, top_i = reference(cfg, lp, x)
    assert (top_i == 1).any(axis=1).all()
    tm = moe_tiles.tile_rows(B * S * cfg.num_experts_per_tok,
                             cfg.num_experts, cfg.router_width)
    assert B * S > 2 * tm and B * S > (B * S) // 4
    got, stats = tiles(cfg, lp, x)
    # (sums of tens here: the made direction is 40 long)
    np.testing.assert_allclose(got.reshape(B * S, -1), want, rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(got, buckets(cfg, lp, x), rtol=2e-5,
                               atol=2e-5)
    assert stats[1] == 0 and stats[3] == _rows(cfg, top_i, B * S)
    assert stats[3] < cfg.num_experts * B * S     # the all-T buckets' rows


def test_four_shares_of_the_router_add_up_to_the_whole(layer):
    """Four chips that hold experts 0-3, 4-7, 8-11, 12-15 of one layer:
    their held parts add up to the layer that holds all sixteen, and
    their held pairs to every pair routed."""
    cfg, lp = layer
    whole_cfg = cfg.with_(name=cfg.name + "-e16", num_experts=16)
    model, tree = FAMILIES[cfg.name]
    params = model.init_params(whole_cfg, jax.random.PRNGKey(13),
                               dtype=jnp.float32)
    lp = dict(_layer_view(params[tree], jnp.asarray(0, jnp.int32)))
    x = _x(cfg, 2, 20, seed=9)
    whole, st = tiles(whole_cfg, lp, x)
    assert st[0] == st[2] == 2 * 20 * cfg.num_experts_per_tok
    total, held = 0.0, 0
    experts = ("w_up_e", "w_down") if cfg.mlp_activation == "relu2" else (
        "wgu_e", "w_down")
    for share in range(4):
        ids = jnp.arange(4 * share, 4 * share + 4)
        order = jnp.concatenate([ids, jnp.delete(jnp.arange(16), ids)])
        part = {**lp, "router": lp["router"][:, order],
                **{name: lp[name][ids] for name in experts}}
        if "router_bias" in lp:
            part["router_bias"] = lp["router_bias"][order]
        out, st = tiles(cfg, part, x)
        total, held = total + out, held + st[0]
    assert held == 2 * 20 * cfg.num_experts_per_tok
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


def test_relu2_experts_over_tiles_that_name_their_expert():
    """moe_tiles.relu2_experts with ``source``: tile t reads expert
    source[t], as swiglu_experts does for the gated families."""
    rng = np.random.default_rng(3)
    NE, H, F, tm = 3, 16, 24, 8
    w_up = jnp.asarray(rng.standard_normal((NE, H, F)), jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((NE, F, H)), jnp.float32)
    xin = jnp.asarray(rng.standard_normal((5, tm, H)), jnp.float32)
    source = jnp.asarray([0, 0, 2, 2, 1], jnp.int32)
    count = jnp.asarray([8, 3, 8, 8, 1], jnp.int32)
    got = moe_tiles.relu2_experts(xin, count, source, w_up, w_down)
    for t, e in enumerate(np.asarray(source)):
        want = np.square(np.maximum(np.asarray(xin[t]) @ np.asarray(
            w_up[e]), 0.0)) @ np.asarray(w_down[e])
        np.testing.assert_allclose(np.asarray(got[t]), want, rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("tokens,nemotron,latent,free", [
    (1, 64, 64, 16), (5, 64, 64, 16), (64, 64, 64, 16), (256, 64, 64, 16),
    (512, 64, 64, 32), (1024, 64, 64, 64), (2048, 128, 64, 128),
    (4096, 128, 128, 128)])
def test_tile_rows_of_a_held_range_read_shapes_alone(tokens, nemotron,
                                                     latent, free):
    """The rule at the two benchmark configurations' widths (128 of 512
    experts top-22; 16 of 256 top-8), by the tokens of a dispatch (a
    wake, the buckets, a two-row chunk, a long generate): the rule on
    the pairs the held experts expect (``free``: what that alone gives
    Nemotron), and no fewer than 64 rows (PR 43 measured 16 to 128 at
    256 and 512 tokens). A caller that holds every expert its router
    scores gets the rule it had."""
    assert moe_tiles.tile_rows(tokens * 22, 128, 512) == nemotron
    assert moe_tiles.tile_rows(tokens * 8, 16, 256) == latent
    assert moe_tiles.tile_rows(tokens * 22 * 128 // 512, 128) == free
    assert max(free, 64) == nemotron
    for pairs in (tokens * 8, tokens * 2):
        for experts in (8, 64):
            assert moe_tiles.tile_rows(pairs, experts, experts) == \
                moe_tiles.tile_rows(pairs, experts, None) == \
                moe_tiles.tile_rows(pairs, experts)

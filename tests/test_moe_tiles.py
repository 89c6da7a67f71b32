"""The sorted-tile dispatch (models/moe_tiles.py) under the Mixtral
family's router: a dropless prefill on one device computes the pairs it
routed (mixtral._moe_tiles) and agrees with the bucket dispatch it left
(mixtral._moe_mlp) on the same inputs. Float32 on the CPU; the Pallas
kernel with ``source`` runs in interpret mode at OLMoE's tile shapes.
The hybrid family's side of the same function is in
tests/test_mellum_parity.py, and tests/test_program_hashes.py holds
that its programs did not move."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import mixtral, moe_tiles
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.parallel.mesh import MeshConfig, make_mesh

H, F = 32, 16


def _weights(NE, seed=0, fused=False):
    rng = np.random.default_rng(seed)
    router = jnp.asarray(rng.standard_normal((H, NE)), jnp.float32)
    w_gate = jnp.asarray(rng.standard_normal((NE, H, F)) * 0.1, jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((NE, H, F)) * 0.1, jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((NE, F, H)) * 0.1, jnp.float32)
    w_gu = jnp.concatenate([w_gate, w_up], axis=-1) if fused else None
    return router, (w_gate, w_up, w_down), w_gu


def _buckets(x, router, ws, k, w_gu=None, renormalize=True):
    """The bucket dispatch on the same inputs: C = T, every position
    takes a slot (what a dropless prefill ran before PR 42, and what a
    mesh, a capacity and the decode step still run)."""
    return np.asarray(mixtral._moe_mlp(x, router, *ws, k, None, None, None,
                                       w_gu, renormalize, None)[0])


def _x(B, S, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (B, S, H)), jnp.float32)


def _hand_rows(x, router, k, valid, NE):
    """Filled tiles x rows a tile, recounted in numpy from the routing."""
    T = valid.size
    probs = jax.nn.softmax(np.asarray(x).reshape(T, H) @ np.asarray(router),
                           axis=-1)
    top = np.asarray(jax.lax.top_k(probs, k)[1])[valid.reshape(T)]
    sent = np.bincount(top.reshape(-1), minlength=NE)
    tm = moe_tiles.tile_rows(T * k, NE)
    return int(np.sum(-(-sent // tm)) * tm), sent


@pytest.mark.parametrize("renormalize", [True, False],
                         ids=["renormalised", "softmax-weights"])
@pytest.mark.parametrize("fused", [False, True], ids=["gate-up", "wgu"])
@pytest.mark.parametrize("B,S,NE,k", [(1, 64, 8, 4), (2, 64, 8, 4),
                                      (1, 13, 8, 3), (2, 24, 64, 8)],
                         ids=["one-row", "two-rows", "P-not-a-tile-multiple",
                              "64-experts-top-8"])
def test_tiles_agree_with_the_buckets(B, S, NE, k, fused, renormalize):
    """Every position real: the counted (tile) form against the maskless
    (bucket) form, and the count's three entries against a hand count."""
    router, ws, w_gu = _weights(NE, fused=fused)
    x = _x(B, S)
    valid = np.ones((B, S), bool)
    out, stats = mixtral.moe_mlp_counted(x, router, *ws, k, jnp.asarray(valid),
                                         w_gu=w_gu, renormalize=renormalize)
    want = _buckets(x, router, ws, k, w_gu, renormalize)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
    # The maskless form (generate, a verify, a wake) IS the buckets.
    np.testing.assert_array_equal(
        np.asarray(mixtral.moe_mlp(x, router, *ws, k, w_gu=w_gu,
                                   renormalize=renormalize)), want)
    rows, sent = _hand_rows(x, router, k, valid, NE)
    assert list(np.asarray(stats)) == [B * S * k, 0, rows]
    assert B * S * k <= rows < B * S * k + NE * moe_tiles.tile_rows(
        B * S * k, NE)
    if (B * S * k) % moe_tiles.tile_rows(B * S * k, NE):
        assert (B, S) == (1, 13)        # the case its id names


@pytest.mark.parametrize("k,chosen",
                         [(1, (5,)), (2, (1, 6)), (4, (0, 2, 3, 7))],
                         ids=["all-to-one-expert", "two-of-eight",
                              "four-of-eight"])
def test_pairs_all_sent_to_a_few_experts_and_experts_with_none(k, chosen):
    """A router that sends every position to the same k experts: their
    runs are many tiles long, the other experts have no pair and no
    tile, and the output is still the buckets'."""
    NE, B, S = 8, 2, 40
    _, ws, _ = _weights(NE)
    router = np.zeros((H, NE), np.float32)
    x = np.abs(np.asarray(_x(B, S))) + 0.1
    for rank, e in enumerate(chosen):
        router[:, e] = 1.0 + 0.1 * rank       # positive x: these k win
    router = jnp.asarray(router)
    x = jnp.asarray(x)
    valid = np.ones((B, S), bool)
    out, stats = mixtral.moe_mlp_counted(x, router, *ws, k,
                                         jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(out), _buckets(x, router, ws, k),
                               atol=1e-5)
    rows, sent = _hand_rows(x, router, k, valid, NE)
    assert sorted(np.nonzero(sent)[0]) == sorted(chosen)
    assert set(sent[list(chosen)]) == {B * S}
    tm = moe_tiles.tile_rows(B * S * k, NE)
    assert int(stats[2]) == rows == k * -(-B * S // tm) * tm


@pytest.mark.parametrize("lens,real_rows", [
    ((40, 17), (True, True)), ((40, 9), (True, False)),
    ((3, 0), (True, True)), ((0, 0), (False, False))],
    ids=["trailing-padding", "a-dummy-row", "an-empty-row", "all-padding"])
def test_padding_and_dummy_rows_take_no_tile_row(lens, real_rows):
    """``valid`` positions alone take tile rows: padding and a dummy
    entry's row come back 0, are not counted, and a real position's
    output is what it is with every position real."""
    NE, k, B, S = 8, 4, 2, 40
    router, ws, _ = _weights(NE)
    x = _x(B, S)
    valid = ((np.arange(S)[None, :] < np.asarray(lens)[:, None])
             & np.asarray(real_rows)[:, None])
    out, stats = mixtral.moe_mlp_counted(x, router, *ws, k,
                                         jnp.asarray(valid))
    full, _ = mixtral.moe_mlp_counted(x, router, *ws, k,
                                      jnp.ones((B, S), bool))
    out, full = np.asarray(out), np.asarray(full)
    np.testing.assert_allclose(out[valid], full[valid], atol=1e-6)
    assert not out[~valid].any()
    rows, _ = _hand_rows(x, router, k, valid, NE)
    assert list(np.asarray(stats)) == [int(valid.sum()) * k, 0, rows]


def test_only_the_dropless_prefill_on_one_device_leaves_the_buckets():
    """What chooses the path is what the call carries: a capacity, a
    mesh, a one-position step or a step's ``live`` rows keep the buckets
    (and a capacity the two-entry count), and so does every maskless
    call: a verify and a session wake pass no capacity for Mixtral's
    176 MB experts too."""
    NE, k, B, S = 8, 4, 2, 16
    router, ws, _ = _weights(NE)
    x = _x(B, S)
    valid = jnp.asarray(np.arange(S)[None, :] < np.array([[16], [5]]))
    plain = _buckets(x, router, ws, k)
    # A capacity: the parent's vector, and padding is computed as ever.
    out, stats = mixtral.moe_mlp_counted(x, router, *ws, k, valid,
                                         capacity=B * S)
    assert stats.shape == (2,)
    np.testing.assert_array_equal(np.asarray(out), plain)
    # A mesh: buckets of every position for every expert.
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    out, stats = mixtral.moe_mlp_counted(x, router, *ws, k, valid, mesh)
    assert list(np.asarray(stats)) == [21 * k, 0, NE * B * S]
    np.testing.assert_allclose(np.asarray(out), plain, atol=1e-5)
    # S == 1: a step's shape.
    out, stats = mixtral.moe_mlp_counted(x[:, :1], router, *ws, k,
                                         valid[:, :1])
    assert list(np.asarray(stats)) == [2 * k, 0, NE * B]
    np.testing.assert_array_equal(np.asarray(out),
                                  _buckets(x[:, :1], router, ws, k))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(mixtral.moe_mlp(x[:, :1], router, *ws,
                                                    k)))
    # No mask of real positions (a verify's S > 1 with no capacity).
    np.testing.assert_array_equal(
        np.asarray(mixtral.moe_mlp(x, router, *ws, k)), plain)
    # ``live`` rows are a decode step's: buckets, whatever the shape.
    live = jnp.asarray([True, False])
    stepped = np.asarray(mixtral.moe_mlp(x, router, *ws, k, live=live))
    np.testing.assert_array_equal(stepped[0], plain[0])
    assert not stepped[1].any()


@pytest.mark.parametrize("name,width", [("tiny-olmoe", 3), ("tiny-moe", 3),
                                        ("olmoe-1b-7b", 3),
                                        ("mixtral-8x7b", 2),
                                        ("bench-moe", 2)])
def test_the_counts_width_follows_the_capacity_factor(name, width):
    cfg = get_config(name)
    assert mixtral.prefill_stats(cfg) == (
        "assigned", "dropped", "rows")[:width]
    assert mixtral.no_stats(cfg.moe_capacity_factor is None).shape == (
        width,)
    assert mixtral.no_touched().shape == (2,)       # decode's: unchanged


@pytest.mark.parametrize("pairs,experts,rows", [
    (256 * 8, 64, 64), (512 * 8, 64, 64), (1024 * 8, 64, 128),
    (2048 * 8, 64, 128), (128 * 8, 64, 32), (48 * 8, 64, 16),
    (256 * 2, 8, 64), (64 * 4, 8, 32), (4, 8, 8)],
    ids=["olmoe-one-row", "olmoe-a-pair", "mellum-chunk", "olmoe-2048",
         "olmoe-128-bucket", "fewer-pairs-than-experts-x-8",
         "mixtral-shaped", "tiny-olmoe", "floor"])
def test_tile_rows_are_a_function_of_the_shapes(pairs, experts, rows):
    assert moe_tiles.tile_rows(pairs, experts) == rows


@pytest.mark.parametrize("Hin,O", [(2048, 2048), (1024, 2048)],
                         ids=["gate-up-2048-2048", "down-1024-2048"])
def test_expert_kernel_walks_an_olmoe_shaped_tile(Hin, O):
    """The expert-stripe kernel (interpret mode) with ``source`` at
    OLMoE's projection shapes and its one-row tile of 64 rows: five
    tiles over three experts' runs (the second expert's run is two
    tiles, the last tile is empty)."""
    from p2p_llm_chat_tpu.ops import quant_mm as qmm
    rng = np.random.default_rng(0)
    L, NE, tm = 1, 3, moe_tiles.tile_rows(256 * 8, 64)
    assert tm == 64 and qmm.pick_expert_bo(tm, Hin, O, 4) is not None
    q = jnp.asarray(rng.integers(-127, 128, size=(L, NE, Hin, O)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.5, 1.5, size=(L, NE, 1, O)) * 1e-3,
                    jnp.float32)
    source = jnp.asarray([0, 1, 1, 2, 2], jnp.int32)
    count = jnp.asarray([64, 64, 7, 19, 0], jnp.int32)
    x = np.asarray(rng.standard_normal((5, tm, Hin)), np.float32)
    x *= (np.arange(tm)[None, :, None] < np.asarray(count)[:, None, None])
    got = qmm.quant_matmul_experts_stacked(jnp.asarray(x), q, s, 0, count,
                                           source=source, interpret=True)
    w = np.asarray(q[0], np.float32) * np.asarray(s[0])
    want = np.einsum("tch,tho->tco", x, w[np.asarray(source)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-3)
    assert not np.asarray(got)[4].any()

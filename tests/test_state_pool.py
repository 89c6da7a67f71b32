"""ops/state_pool.py at the op: the chunked (SSD) scan is the sequential
recurrence in float32, whatever the length and the block; padding
neither moves the state nor enters the convolution's window; the pool's
writes name whole rows; a decode update leaves rows that are not live
bit-equal; and the programs over the pool update it in place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops import state_pool
from p2p_llm_chat_tpu.ops.state_pool import StatePool

CFG = get_config("tiny-nemotron-h")
H, P, G, N = 8, 16, 2, 16


def draws(B, S, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.5))
    Bm = jax.random.normal(ks[3], (B, S, G, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, G, N), jnp.float32)
    S0 = jax.random.normal(ks[5], (B, H, P, N), jnp.float32)
    return x, dt, A, Bm, Cm, S0


def sequential(x, dt, A, Bm, Cm, S0):
    ys, S = [], S0
    for t in range(x.shape[1]):
        y, S = state_pool.ssm_step(S, x[:, t], dt[:, t], A, Bm[:, t],
                                   Cm[:, t])
        ys.append(y)
    return jnp.stack(ys, axis=1), S


@pytest.mark.parametrize("S,chunk", [(1, 16), (7, 16), (16, 16), (37, 16),
                                     (48, 16), (50, 128), (33, 8)])
def test_chunked_scan_is_the_sequential_recurrence(S, chunk):
    x, dt, A, Bm, Cm, S0 = draws(2, S, seed=S)
    y_seq, S_seq = sequential(x, dt, A, Bm, Cm, S0)
    y, S_out = state_pool.ssd_scan(x, dt, A, Bm, Cm, S0, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(S_out), np.asarray(S_seq),
                               rtol=2e-4, atol=2e-4)


def test_a_scan_in_pieces_is_the_scan_in_one():
    """State handed from piece to piece, at edges that are no multiple
    of the block."""
    x, dt, A, Bm, Cm, S0 = draws(2, 45, seed=3)
    y_all, S_all = state_pool.ssd_scan(x, dt, A, Bm, Cm, S0, 16)
    S, ys = S0, []
    for lo, hi in ((0, 13), (13, 30), (30, 45)):
        y, S = state_pool.ssd_scan(x[:, lo:hi], dt[:, lo:hi], A,
                                   Bm[:, lo:hi], Cm[:, lo:hi], S, 16)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_all), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_all),
                               rtol=2e-4, atol=2e-4)


def test_positions_with_dt_zero_do_not_move_the_state():
    x, dt, A, Bm, Cm, S0 = draws(2, 40, seed=5)
    lens = jnp.asarray([23, 40])
    real = jnp.arange(40)[None, :] < lens[:, None]
    _, S_pad = state_pool.ssd_scan(x, jnp.where(real[..., None], dt, 0.0),
                                   A, Bm, Cm, S0, 16)
    _, S_row0 = state_pool.ssd_scan(x[:1, :23], dt[:1, :23], A, Bm[:1, :23],
                                    Cm[:1, :23], S0[:1], 16)
    np.testing.assert_allclose(np.asarray(S_pad[0]), np.asarray(S_row0[0]),
                               rtol=2e-4, atol=2e-4)


def test_convolution_scan_is_its_steps_and_its_window_skips_padding():
    K, C, B, S = 4, 12, 3, 9
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    xbc = jax.random.normal(ks[0], (B, S, C), jnp.float32)
    win0 = jax.random.normal(ks[1], (B, K - 1, C), jnp.float32)
    w = jax.random.normal(ks[2], (K, C), jnp.float32)
    b = jax.random.normal(ks[3], (C,), jnp.float32)
    lens = jnp.asarray([9, 4, 0])
    out, win = state_pool.conv_scan(xbc, win0, lens, w, b)
    wins, outs, cur = [], [], win0
    for t in range(S):
        o, cur = state_pool.conv_step(cur, xbc[:, t], w, b)
        outs.append(o)
        wins.append(cur)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.stack(outs, 1)), rtol=1e-5,
                               atol=1e-5)
    # The window after each row's last REAL position.
    assert np.array_equal(np.asarray(win[0]), np.asarray(wins[8][0]))
    assert np.array_equal(np.asarray(win[1]), np.asarray(wins[3][1]))
    assert np.array_equal(np.asarray(win[2]), np.asarray(win0[2]))


def filled_pool(rows, seed=0):
    pool = StatePool.create(CFG, rows, jnp.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return StatePool(ssm=jax.random.normal(k1, pool.ssm.shape, jnp.float32),
                     conv=jax.random.normal(k2, pool.conv.shape, jnp.float32))


def test_write_rows_overwrites_whole_rows_and_dummies_hit_the_garbage_row():
    slots = 4
    pool = filled_pool(slots + 1)
    state = filled_pool(3, seed=9)
    # Entries for slots 2 and 0, and a dummy (row sentinel = num_slots).
    rows = jnp.asarray([2, 0, slots])
    out = state_pool.write_rows(pool, state, rows)
    for got, src, old in ((out.ssm, state.ssm, pool.ssm),
                          (out.conv, state.conv, pool.conv)):
        got, src, old = map(np.asarray, (got, src, old))
        assert np.array_equal(got[:, 2], src[:, 0])
        assert np.array_equal(got[:, 0], src[:, 1])
        assert np.array_equal(got[:, slots], src[:, 2])
        assert np.array_equal(got[:, 1], old[:, 1])
        assert np.array_equal(got[:, 3], old[:, 3])


def test_snapshot_round_trip_seeds_every_row():
    state = filled_pool(1, seed=2)
    snap = state_pool.snapshot(state)
    assert snap.ssm.shape == state.ssm.shape[:1] + state.ssm.shape[2:]
    back = state_pool.from_snapshot(snap, 3)
    for r in range(3):
        assert np.array_equal(np.asarray(back.ssm[:, r]),
                              np.asarray(state.ssm[:, 0]))
        assert np.array_equal(np.asarray(back.conv[:, r]),
                              np.asarray(state.conv[:, 0]))


def _step_program():
    d = CFG.mamba_inner
    A = -jnp.ones((H,), jnp.float32)

    def split(conv_out):
        lead = conv_out.shape[:-1]
        return (conv_out[..., :d].reshape(*lead, H, P),
                jnp.full(lead + (H,), 0.1, jnp.float32), A,
                conv_out[..., d: d + G * N].reshape(*lead, G, N),
                conv_out[..., d + G * N:].reshape(*lead, G, N))

    def step(pool, live, xbc, w, b):
        for layer in range(2):
            _, _, pool = state_pool.decode_update(
                pool, jnp.asarray(layer, jnp.int32), live, xbc, w, b, split)
        return pool

    return step


def test_decode_update_leaves_rows_that_are_not_live_bit_equal():
    B = 3
    pool = filled_pool(B + 1, seed=4)
    xbc = jax.random.normal(jax.random.PRNGKey(8), (B, CFG.conv_dim))
    w = jnp.ones((CFG.conv_kernel, CFG.conv_dim), jnp.float32)
    b = jnp.zeros((CFG.conv_dim,), jnp.float32)
    live = jnp.asarray([True, False, True])
    out = jax.jit(_step_program())(pool, live, xbc, w, b)
    for new, old in ((out.ssm, pool.ssm), (out.conv, pool.conv)):
        new, old = np.asarray(new), np.asarray(old)
        assert np.array_equal(new[:2, 1], old[:2, 1])       # not live
        assert np.array_equal(new[:2, B], old[:2, B])       # garbage row
        assert np.array_equal(new[2:], old[2:])             # other layers
        assert not np.array_equal(new[:2, 0], old[:2, 0])
        assert not np.array_equal(new[:2, 2], old[:2, 2])


@pytest.mark.parametrize("program", ["decode_update", "write_rows"])
def test_pool_programs_alias_the_donated_pool(program):
    """The compiled program's output pool IS its input pool's buffer
    (``input_output_alias`` in the optimised module). Whether the TPU
    compiler also keeps every instruction in place is read off the
    served programs' optimised HLO on the chip
    (tools/check_pool_copies.py): the CPU compiler's copies say nothing
    about it."""
    B = 3
    pool = StatePool.create(CFG, B + 1, jnp.float32)
    if program == "decode_update":
        fn = jax.jit(_step_program(), donate_argnums=(0,))
        args = (pool, jnp.ones((B,), bool), jnp.zeros((B, CFG.conv_dim)),
                jnp.ones((CFG.conv_kernel, CFG.conv_dim)),
                jnp.zeros((CFG.conv_dim,)))
    else:
        fn = jax.jit(state_pool.write_rows, donate_argnums=(0,))
        args = (pool, StatePool.create(CFG, 2, jnp.float32),
                jnp.asarray([1, B]))
    text = fn.lower(*args).compile().as_text()
    assert "input_output_alias" in text
